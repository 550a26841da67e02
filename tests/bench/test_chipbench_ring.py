"""The four-chip ring cell on four virtual CPU devices: sound, then broken.

The sharded path needs a mesh, and the main test process keeps JAX's one
CPU device, so a child process with four host devices runs the tiny ring
cell twice: as it is, and with the halo exchange between chips left out
(``ppermute`` returns each shard's own columns).  The first run is correct,
the second is not.
"""
import json
import os
import subprocess
import sys
import textwrap

from chipbench_tiny import ROOT

CHILD = textwrap.dedent("""
    import json, sys
    sys.path[0:0] = [sys.argv[1], sys.argv[2]]
    import jax
    import chipbench_tiny as tiny
    layout = tiny.tiny_layout(sys.argv[3])
    sound = tiny.run_tiny(layout, "t.ring", seconds=1.0)
    jax.lax.ppermute = lambda x, axis_name, perm: x
    broken = tiny.run_tiny(layout, "t.ring", seconds=1.0)
    print(json.dumps({"sound": sound, "broken": broken}))
""")


def test_ring_cell_is_correct_and_fails_without_the_exchange(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", CHILD, str(ROOT / "src"),
         str(ROOT / "tests" / "bench"), str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    sound, broken = out["sound"], out["broken"]
    assert sound["device"]["count"] == 4
    assert sound["correct"] is True, sound["checks"]
    gap = broken["checks"]["records_rel_gap"]
    assert broken["correct"] is False and gap["value"] > gap["limit"]
    assert set(sound["metrics"]) == {"pe_steps_per_s", "setup_s"}
