"""Jaxpr flattening: one dataflow graph across every nesting construct.

``jax.make_jaxpr`` gives a *nested* program — ``jit`` / ``scan`` / ``cond``
/ ``shard_map`` / ``pallas_call`` equations each carry sub-jaxprs with their
own variable namespaces.  The rules want plain dataflow questions ("does the
tau output depend on a roll by 2", "is there a float psum on the tau path"),
so this module inlines everything into a single :class:`Graph` of
:class:`Node`\\ s with global ids.

Inlining semantics (what the rules rely on):

* ``jit`` / ``closed_call`` / ``custom_jvp_call`` / ``checkpoint``: transparent —
  the body is spliced in, provenance path extended with the jit name.
* ``scan`` / ``while``: the body is inlined **once**.  Each carry component
  gets a synthetic ``scan_carry`` node (dep: the init value) whose
  ``params["carry_out"]`` is patched to the body's output for that slot —
  rules formulate per-step invariants (e.g. stencil growth per step) against
  these pairs.  ``xs`` inputs appear as ``scan_xs`` (leading axis dropped),
  stacked ys outputs as ``scan_stack``.
* ``cond``: all branches are inlined; every output becomes a ``cond_join``
  node over the predicate and the per-branch values.  Branches that mutate
  refs (``pl.when``) join the final cell values the same way.
* ``shard_map``: body inlined; operands enter via ``shard_in`` nodes (aval
  becomes the shard-local block) and leave via ``shard_out``.
* ``pallas_call``: the kernel jaxpr is inlined with *ref-cell* semantics:
  each input ref's cell starts at a ``pallas_block`` node wrapping the
  operand, each output ref's cell starts at a synthetic ``ref_carry`` node
  (the revisited-tile fixpoint seed — same role as ``scan_carry``);
  ``get`` reads the cell, ``swap`` writes it, and the call's outputs are
  ``pallas_out`` nodes over the final cells.  The ``pallas_call`` node
  itself is kept (deps: operands) carrying ``grid_mapping`` for the VMEM
  rule.

The graph is an over-approximation: a rule that finds *no* violating path
has proven the invariant for the traced shapes; unknown constructs degrade
to conservative "unanalyzable" nodes rather than silently passing.
"""
from __future__ import annotations

import dataclasses
from typing import Any

from jax.extend.core import Literal


@dataclasses.dataclass
class Node:
    gid: int
    prim: str
    deps: list
    aval: Any = None          # output ShapedArray (or None)
    params: dict = dataclasses.field(default_factory=dict)
    path: str = ""            # provenance: nesting path, e.g. "/one/scan"
    src: str = ""             # best-effort source location "file:line"

    def describe(self) -> str:
        shape = getattr(self.aval, "shape", None)
        dt = getattr(self.aval, "dtype", None)
        s = f"{self.prim}"
        if shape is not None:
            s += f" -> {dt}{list(shape)}"
        return s


class _RefCell:
    """Mutable cell standing in for a pallas ref during inlining."""

    __slots__ = ("cell",)

    def __init__(self, cell: int):
        self.cell = cell


@dataclasses.dataclass
class Graph:
    nodes: list
    in_gids: list
    out_gids: list

    def node(self, gid: int) -> Node:
        return self.nodes[gid]

    def ancestors(self, gid: int) -> set:
        """All gids reachable backwards from ``gid`` (inclusive)."""
        seen, stack = set(), [gid]
        while stack:
            g = stack.pop()
            if g in seen:
                continue
            seen.add(g)
            stack.extend(self.nodes[g].deps)
        return seen

    def find(self, prim: str) -> list:
        return [n for n in self.nodes if n.prim == prim]


def _src_of(eqn) -> str:
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            return f"{frame.file_name.rsplit('/', 1)[-1]}:{frame.start_line}"
    except Exception:
        pass
    return ""


def _inner_aval(aval):
    """AbstractRef -> carried array aval; plain avals pass through."""
    return getattr(aval, "inner_aval", aval)


def _sub_jaxpr(params, *keys):
    for k in keys:
        if k in params and params[k] is not None:
            return params[k]
    return None


def _as_closed(j):
    """(jaxpr, consts) from either a ClosedJaxpr or a raw Jaxpr."""
    if hasattr(j, "jaxpr"):
        return j.jaxpr, list(j.consts)
    return j, []


class _Builder:
    def __init__(self):
        self.nodes: list[Node] = []

    def add(self, prim, deps, aval=None, params=None, path="", src="") -> int:
        gid = len(self.nodes)
        self.nodes.append(Node(gid, prim, [d for d in deps if d is not None],
                               aval, params or {}, path, src))
        return gid

    # -- one jaxpr body ----------------------------------------------------

    def inline(self, jaxpr, consts, invals, path: str) -> list:
        """Inline ``jaxpr``; invals are gids or _RefCells.  Returns outvals."""
        env: dict = {}

        def read(atom):
            if isinstance(atom, Literal):
                return self.add("const", [], aval=atom.aval,
                                params={"val": atom.val}, path=path)
            return env[atom]

        for var, cval in zip(jaxpr.constvars, consts):
            aval = getattr(cval, "aval", None) or getattr(var, "aval", None)
            env[var] = self.add("const", [], aval=aval,
                                params={"val": cval}, path=path)
        for var, v in zip(jaxpr.invars, invals):
            env[var] = v

        for eqn in jaxpr.eqns:
            invals_e = [read(a) for a in eqn.invars]
            outs = self.eqn(eqn, invals_e, path)
            for var, o in zip(eqn.outvars, outs):
                if type(var).__name__ != "DropVar":
                    env[var] = o
        return [read(v) for v in jaxpr.outvars]

    # -- one equation ------------------------------------------------------

    def eqn(self, eqn, invals, path: str) -> list:
        name = eqn.primitive.name
        src = _src_of(eqn)
        params = dict(eqn.params)
        out_avals = [v.aval for v in eqn.outvars]

        if name in ("jit", "closed_call", "checkpoint", "custom_jvp_call",
                    "custom_vjp_call"):
            sub = _sub_jaxpr(params, "jaxpr", "call_jaxpr", "fun_jaxpr")
            if sub is not None:
                j, consts = _as_closed(sub)
                label = params.get("name", name)
                return self.inline(j, consts, invals, f"{path}/{label}")

        if name == "scan":
            return self._scan(eqn, invals, path, src)
        if name == "while":
            return self._while(eqn, invals, path, src)
        if name == "cond":
            return self._cond(eqn, invals, path, src)
        if name == "shard_map":
            return self._shard_map(eqn, invals, path, src)
        if name == "pallas_call":
            return self._pallas(eqn, invals, path, src)

        if name == "get":
            ref = invals[0]
            if isinstance(ref, _RefCell):
                extra = [v for v in invals[1:] if not isinstance(v, _RefCell)]
                g = self.add("ref_get", [ref.cell] + extra,
                             aval=out_avals[0], params=params,
                             path=path, src=src)
                return [g]
        if name == "swap":
            ref, val = invals[0], invals[1]
            if isinstance(ref, _RefCell):
                old = ref.cell
                extra = [v for v in invals[2:] if not isinstance(v, _RefCell)]
                ref.cell = self.add("ref_swap", [val] + extra,
                                    aval=_inner_aval(eqn.invars[0].aval),
                                    params=params, path=path, src=src)
                return [self.add("ref_get", [old], aval=out_avals[0],
                                 path=path, src=src)]

        deps = [v.cell if isinstance(v, _RefCell) else v for v in invals]
        gid = self.add(name, deps, aval=out_avals[0] if out_avals else None,
                       params=params, path=path, src=src)
        if len(out_avals) <= 1:
            return [gid]
        return [self.add("proj", [gid], aval=a,
                         params={"index": i}, path=path, src=src)
                for i, a in enumerate(out_avals)]

    # -- structured constructs --------------------------------------------

    def _scan(self, eqn, invals, path, src):
        p = eqn.params
        j, consts = _as_closed(p["jaxpr"])
        nc, ncar = p["num_consts"], p["num_carry"]
        cvals = invals[:nc]
        carry_nodes = []
        body_in = list(cvals)
        for i, init in enumerate(invals[nc:nc + ncar]):
            g = self.add("scan_carry", [init],
                         aval=j.invars[nc + i].aval,
                         params={"slot": i}, path=path, src=src)
            carry_nodes.append(g)
            body_in.append(g)
        for i, xs in enumerate(invals[nc + ncar:]):
            body_in.append(self.add("scan_xs", [xs],
                                    aval=j.invars[nc + ncar + i].aval,
                                    path=path, src=src))
        outs = self.inline(j, consts, body_in, f"{path}/scan")
        carry_out, ys = outs[:ncar], outs[ncar:]
        for g, co in zip(carry_nodes, carry_out):
            self.nodes[g].params["carry_out"] = co
        res = list(carry_out)
        for i, y in enumerate(ys):
            res.append(self.add("scan_stack", [y],
                                aval=eqn.outvars[ncar + i].aval,
                                path=path, src=src))
        return res

    def _while(self, eqn, invals, path, src):
        p = eqn.params
        cj, cconsts = _as_closed(p["cond_jaxpr"])
        bj, bconsts = _as_closed(p["body_jaxpr"])
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        carry_init = invals[cn + bn:]
        carry_nodes = [
            self.add("scan_carry", [init], aval=v.aval,
                     params={"slot": i}, path=path, src=src)
            for i, (init, v) in enumerate(
                zip(carry_init, bj.invars[bn:]))]
        self.inline(cj, cconsts, invals[:cn] + carry_nodes, f"{path}/while_cond")
        outs = self.inline(bj, bconsts, invals[cn:cn + bn] + carry_nodes,
                           f"{path}/while")
        for g, co in zip(carry_nodes, outs):
            self.nodes[g].params["carry_out"] = co
        return outs

    def _cond(self, eqn, invals, path, src):
        branches = eqn.params["branches"]
        pred, ops = invals[0], invals[1:]
        ref_slots = [i for i, v in enumerate(ops) if isinstance(v, _RefCell)]
        snapshot = {i: ops[i].cell for i in ref_slots}
        branch_outs, branch_cells = [], []
        for bi, br in enumerate(branches):
            j, consts = _as_closed(br)
            for i in ref_slots:          # each branch starts from the snapshot
                ops[i].cell = snapshot[i]
            outs = self.inline(j, consts, ops, f"{path}/cond{bi}")
            branch_outs.append(outs)
            branch_cells.append({i: ops[i].cell for i in ref_slots})
        for i in ref_slots:
            cells = [bc[i] for bc in branch_cells]
            if len(set(cells)) > 1:
                ops[i].cell = self.add(
                    "cond_join", [pred] + cells,
                    aval=_inner_aval(eqn.invars[1 + i].aval),
                    path=path, src=src)
        res = []
        for k, var in enumerate(eqn.outvars):
            vals = [bo[k] for bo in branch_outs]
            if len(set(vals)) == 1:
                res.append(vals[0])
            else:
                res.append(self.add("cond_join", [pred] + vals,
                                    aval=var.aval, path=path, src=src))
        return res

    def _shard_map(self, eqn, invals, path, src):
        p = eqn.params
        j, consts = _as_closed(p["jaxpr"])
        in_names = p.get("in_names") or [{}] * len(invals)
        body_in = [
            self.add("shard_in", [v], aval=var.aval,
                     params={"names": dict(n) if hasattr(n, "items") else n},
                     path=path, src=src)
            for v, var, n in zip(invals, j.invars, in_names)]
        outs = self.inline(j, consts, body_in, f"{path}/shard_map")
        return [self.add("shard_out", [o], aval=var.aval, path=path, src=src)
                for o, var in zip(outs, eqn.outvars)]

    def _pallas(self, eqn, invals, path, src):
        p = eqn.params
        j, consts = _as_closed(p["jaxpr"])
        n_out = len(eqn.outvars)
        n_in = len(invals)
        # keep the call node itself: the VMEM rule reads grid_mapping off it
        call = self.add("pallas_call", list(invals), aval=None,
                        params={"grid_mapping": p.get("grid_mapping"),
                                "name": getattr(
                                    p.get("name_and_src_info", None), "name",
                                    p.get("name", ""))},
                        path=path, src=src)
        cells = []
        for i, v in enumerate(invals):
            aval = _inner_aval(j.invars[i].aval)
            cells.append(_RefCell(self.add(
                "pallas_block", [v], aval=aval,
                params={"operand": i}, path=path, src=src)))
        out_cells, seeds = [], []
        for i in range(n_out):
            aval = _inner_aval(j.invars[n_in + i].aval)
            seed = self.add("ref_carry", [], aval=aval,
                            params={"slot": i}, path=path, src=src)
            seeds.append(seed)
            c = _RefCell(seed)
            out_cells.append(c)
            cells.append(c)
        kname = self.nodes[call].params["name"] or "kernel"
        self.inline(j, consts, cells, f"{path}/pallas:{kname}")
        res = []
        for i, c in enumerate(out_cells):
            # the revisited-tile fixpoint: seed's carry_out = final cell value
            self.nodes[seeds[i]].params["carry_out"] = c.cell
            res.append(self.add("pallas_out", [c.cell, call],
                                aval=eqn.outvars[i].aval, path=path, src=src))
        return res


def build_graph(closed_jaxpr) -> Graph:
    """Flatten a ClosedJaxpr from ``jax.make_jaxpr`` into a :class:`Graph`."""
    b = _Builder()
    j = closed_jaxpr.jaxpr
    in_gids = [b.add("input", [], aval=v.aval, params={"index": i})
               for i, v in enumerate(j.invars)]
    out_gids = b.inline(j, list(closed_jaxpr.consts), in_gids, "")
    # outputs may be _RefCells in pathological cases; resolve
    out_gids = [o.cell if isinstance(o, _RefCell) else o for o in out_gids]
    return Graph(b.nodes, in_gids, out_gids)


def ring_axis_of(aval, ring_widths) -> int | None:
    """Axis index whose extent is a known ring width, else None.

    Probe shapes are chosen so ring widths collide with no other extent,
    making this lookup unambiguous (see probes.py).
    """
    shape = getattr(aval, "shape", None)
    if not shape:
        return None
    for ax in range(len(shape) - 1, -1, -1):   # ring rides the minor axis
        if shape[ax] in ring_widths:
            return ax
    return None
