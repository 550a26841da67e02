"""``trace_reduce`` on a small trace laid out as a TPU profile is.

The trace is written from text: two ``/device:TPU:<n>`` planes with their
``XLA Ops`` lines, an ``XLA Modules`` line that must not count, and the
host plane with the ``window`` span and one labelling span.
"""
import numpy as np
import pytest

from chipbench_tiny import DATA  # noqa: F401  (puts the repo on sys.path)
from benchmarks.chip import trace_reduce

XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 4000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 13000000 } }
  event_metadata { key: 1 value { id: 1 name: "kernel.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 3000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "kernel.1" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 8000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "start_trace" } }
  event_metadata { key: 2 value { id: 2 name: "window" } }
  event_metadata { key: 3 value { id: 3 name: "generator_wait" } } }
"""


def test_load_clips_ops_to_the_window_and_keeps_host_spans(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    tr = trace_reduce.load(str(path))
    # window: host ns 3000..11000 -> 8 us
    assert tr.window_s == pytest.approx(8e-6)
    names, s, e = tr.ops[0]
    assert names == ["kernel.1", "all-reduce.2", "kernel.1"]
    assert np.allclose(s * 1e6, [0.0, 2.0, 7.0])
    assert np.allclose(e * 1e6, [1.0, 3.0, 8.0])
    assert sorted(tr.ops) == [0, 1]
    assert tr.host == [("generator_wait", pytest.approx(3e-6),
                        pytest.approx(6e-6))]
    assert trace_reduce.busy_s(tr, [0]) == pytest.approx(3e-6)
    assert trace_reduce.busy_s(tr, [0, 1]) == pytest.approx(2.5e-6)
    assert trace_reduce.exposed_collective_s(tr, [0]) == pytest.approx(1e-6)
    gaps = trace_reduce.idle_gaps(tr, 0)
    assert gaps[0] == ["generator_wait", pytest.approx(4e-6)]


def test_interval_arithmetic():
    iv = trace_reduce.merge(np.array([3.0, 0.0, 1.0]),
                            np.array([4.0, 2.0, 1.5]))
    assert iv.tolist() == [[0.0, 2.0], [3.0, 4.0]]
    assert trace_reduce.length(iv) == 3.0
    cut = trace_reduce.subtract(iv, np.array([[1.0, 3.5]]))
    assert cut.tolist() == [[0.0, 1.0], [3.5, 4.0]]
    assert trace_reduce.subtract(iv, np.zeros((0, 2))).tolist() == \
        iv.tolist()
