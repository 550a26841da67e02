"""The one traffic generator: turns a mix's parameters into sweep requests.

A mix is a JSON file under ``traffic/``; the generator reads it with the
cell's configuration and the run's ``--seed`` and nothing else.  Every
request carries the fields ``spec_for`` builds from the configuration and
the mix, then the configuration's optional ``sweep_fields``: further
``WindowSweep`` fields (a payload's parameters, ``rd_mode``) that go into
every request, warm-up requests too.  Two loops:

* ``closed``: ``clients`` requesters; each sends its next request (a copy of
  the mix's ``request``, with a fresh stream seed) when the previous one is
  answered, until the window closes.
* ``open``: sessions arrive at ``session_rate_per_s``.  Each session sends
  one ``request`` at its arrival time ``t0`` and, for a fixed share of the
  sessions, the mix's ``followups``: ``prefix`` (the first ``blocks`` Δ
  blocks of the same stream), ``duplicate`` (the same request again) or
  ``longer`` (the same stream measured over ``n_steps``), each from another
  tenant, after a delay drawn from ``delay_s``.

Every seed gets the same work: the inter-arrival gaps are the quantiles of
the exponential distribution, and the follow-up shares, prefix lengths and
delays are fixed sets, each put in the seed's own order.  Requesters are
tenants drawn Zipf(``zipf_s``) over ``tenants``.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

#: stream seeds are drawn below 2**31 so that they fit every integer field
SEED_RANGE = (1, 2**31 - 1)


class BenchError(Exception):
    """The run cannot be made here; no result is printed."""


@dataclasses.dataclass
class Request:
    """One request of a run: when it is due, who sends it, what it asks."""

    due: float             # seconds after the window opened
    requester: str
    spec: dict             # WindowSweep fields (deltas as floats)
    kind: str = "study"
    client: int | None = None   # closed loop: the client that sends it


def spec_for(config: dict, request: dict, seed: int) -> dict:
    """WindowSweep fields of one request under ``config``.

    The configuration's ``sweep_fields`` come last; one that would set a
    field built here is refused.  ``WindowSweep`` refuses unknown ones.
    """
    spec = dict(
        Ls=(int(config["L"]),), n_vs=(int(config["n_v"]),),
        deltas=tuple(math.inf if d == "inf" else float(d)
                     for d in config["deltas"]),
        replicas=int(request["replicas"]), n_steps=int(request["n_steps"]),
        burn_in=int(request["burn_in"]), backend=config["backend"],
        window=config["window"], k_fuse=int(config["k_fuse"]),
        steady_frac=float(request.get("steady_frac", 0.5)), seed=int(seed))
    extra = config.get("sweep_fields", {})
    clash = sorted(set(extra) & set(spec))
    if clash:
        raise BenchError(f"sweep_fields {clash} of config "
                         f"{config.get('name')!r} would set fields the "
                         f"harness builds itself ({sorted(spec)})")
    spec.update(extra)
    return spec


def pe_steps(spec: dict) -> int:
    """PE-steps a request asks for: rows x L x (burn-in + measured steps)."""
    rows = len(spec["deltas"]) * spec["replicas"]
    return rows * sum(spec["Ls"]) * len(spec["n_vs"]) * (
        spec["burn_in"] + spec["n_steps"])


def _seeds(rng, n: int) -> list[int]:
    out: list[int] = []
    seen: set[int] = set()
    while len(out) < n:
        s = int(rng.integers(*SEED_RANGE))
        if s not in seen:
            seen.add(s)
            out.append(s)
    return out


class ClosedLoop:
    """``clients`` requesters, each waiting for its answer before the next."""

    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix, self.config = mix, config
        self.rng = np.random.default_rng(seed)
        self.used: set[int] = set()

    def _next(self, client: int, due: float) -> Request:
        (s,) = _seeds(self.rng, 1)
        while s in self.used:
            (s,) = _seeds(self.rng, 1)
        self.used.add(s)
        return Request(due=due, requester=f"client{client}",
                       spec=spec_for(self.config, self.mix["request"], s),
                       client=client)

    def initial(self) -> list[Request]:
        return [self._next(c, 0.0) for c in range(int(self.mix["clients"]))]

    def after_answer(self, req: Request, now: float,
                     seconds: float) -> list[Request]:
        """The client's next request, if the window is still open."""
        return [self._next(req.client, now)] if now < seconds else []


def _zipf_tenants(rng, n: int, tenants: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, tenants + 1) ** s
    return rng.choice(tenants, size=n, p=w / w.sum())


def _fixed_share(rng, n: int, p: float) -> np.ndarray:
    """A boolean mask with exactly round(p * n) True entries, seed-ordered."""
    mask = np.zeros(n, bool)
    mask[:int(round(p * n))] = True
    return rng.permutation(mask)


def _fixed_uniform(rng, n: int, lo: float, hi: float) -> np.ndarray:
    """The n mid-quantiles of U(lo, hi), in the seed's order."""
    return rng.permutation(lo + (hi - lo) * (np.arange(n) + 0.5) / n)


def sessions(mix: dict, config: dict, seed: int, n: int) -> list[Request]:
    """The requests of ``n`` open-loop sessions, in due order."""
    rng = np.random.default_rng(seed)
    rate = float(mix["session_rate_per_s"])
    # exponential quantiles: the same set of gaps for every seed
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    arrivals = np.cumsum(rng.permutation(gaps)) - gaps.min()
    tenants = int(mix["tenants"])
    who = _zipf_tenants(rng, n, tenants, float(mix["zipf_s"]))
    seeds = _seeds(rng, n)
    out = [Request(float(arrivals[i]), f"tenant{who[i]}",
                   spec_for(config, mix["request"], seeds[i]))
           for i in range(n)]
    for f in mix.get("followups", []):
        idx = np.flatnonzero(_fixed_share(rng, n, float(f["p"])))
        delays = _fixed_uniform(rng, len(idx), *map(float, f["delay_s"]))
        other = (who[idx] + 1 + rng.integers(0, tenants - 1, len(idx))) \
            % tenants
        if f["kind"] == "prefix":
            lo, hi = map(int, f["blocks"])
            blocks = rng.permutation(
                lo + np.arange(len(idx)) % (hi - lo + 1))
        for j, i in enumerate(idx):
            spec = dict(out[i].spec)
            if f["kind"] == "prefix":
                spec["deltas"] = spec["deltas"][:int(blocks[j])]
            elif f["kind"] == "longer":
                spec["n_steps"] = int(f["n_steps"])
            elif f["kind"] != "duplicate":
                raise ValueError(f"unknown follow-up kind {f['kind']!r}")
            out.append(Request(float(arrivals[i] + delays[j]),
                               f"tenant{other[j]}", spec, kind=f["kind"]))
    out.sort(key=lambda r: r.due)
    return out


def open_schedule(mix: dict, config: dict, seed: int,
                  seconds: float) -> list[Request]:
    """Every request of an open-loop run that is due before ``seconds``."""
    n = max(1, int(round(float(mix["session_rate_per_s"]) * seconds)))
    return [r for r in sessions(mix, config, seed, n) if r.due < seconds]


def warmup_batches(mix: dict, config: dict) -> list[list[Request]]:
    """Requests that drive every pass shape the mix can produce, in order.

    Each batch is served to the end before the next is submitted, on stream
    seeds that the timed runs never draw (they are below ``SEED_RANGE``).
    """
    base = Request(0.0, "warmup", spec_for(config, mix["request"], 0))
    if mix["loop"] == "closed":
        return [[base]]
    batches = [[base]]
    for f in mix.get("followups", []):
        if f["kind"] == "prefix":
            lo, hi = map(int, f["blocks"])
            for b in range(lo, hi + 1):
                spec = dict(base.spec, deltas=base.spec["deltas"][:b])
                batches.append([Request(0.0, "warmup", spec, kind="prefix")])
        elif f["kind"] == "longer":
            spec = dict(base.spec, n_steps=int(f["n_steps"]))
            batches.append([Request(0.0, "warmup", spec, kind="longer")])
    return batches


class Source:
    """The requests of one run, released as they fall due.

    ``seconds`` is the window: no request falls due after it.
    """

    def __init__(self, mix: dict, config: dict, seed: int, seconds: float):
        self.seconds = seconds
        if mix["loop"] == "closed":
            self.loop = ClosedLoop(mix, config, seed)
            self.queue = self.loop.initial()
        elif mix["loop"] == "open":
            self.loop = None
            self.queue = open_schedule(mix, config, seed, seconds)
        else:
            raise ValueError(f"unknown loop {mix['loop']!r}")

    def take_due(self, now: float) -> list[Request]:
        n = 0
        while n < len(self.queue) and self.queue[n].due <= now:
            n += 1
        due, self.queue = self.queue[:n], self.queue[n:]
        return due

    def next_due(self) -> float | None:
        return self.queue[0].due if self.queue else None

    def answered(self, req: Request, now: float) -> None:
        if self.loop is not None and req.client is not None:
            self.queue.extend(self.loop.after_answer(req, now, self.seconds))
            self.queue.sort(key=lambda r: r.due)
