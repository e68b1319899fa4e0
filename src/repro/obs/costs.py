"""Compiled-cost telemetry: what a scheduled window costs on the
compiler's terms.

The runtime layers (trace.py, stats.py) measure what the protocol *did*;
this module captures what the compiled executors *must* cost, straight
from XLA's ahead-of-time artifacts:

  * ``compiled.cost_analysis()``   — FLOPs and bytes accessed per call,
  * ``compiled.memory_analysis()`` — argument / output / temp buffer
    sizes (the peak-memory decomposition),
  * ``compiled.as_text()``         — post-optimization HLO, from which
    the per-device collective traffic is parsed.

The collective walker adapts ``launch/hlo_analysis.py``'s loop-trip
recovery to the engines' wave loops, with two twists that matter here:

  * The wave / chunk ``while_loop``s have **data-dependent** trip counts
    (``jnp.max(levels) + 1`` and the slab chunk ranges), so no
    ``constant(N)`` appears in the loop condition and static recovery
    returns nothing. Instead each collective is classified by its
    **dynamic-loop nesting depth** (1 = the wave loop, 2 = the split
    rung's chunk loop nested in it), and the *executed* iteration counts
    come from outside — the sharded engine's runtime comm ledger
    (``ShardedEngine.comm_iteration_counts``). Statically-counted loops
    (scan bodies with materialized trips) still multiply in as before.
  * Async collectives appear as ``-start``/``-done`` pairs; only the
    start op carries the transfer, so ``-done`` lines are skipped to
    avoid double counting.

The payoff is a *cross-check identity*: per-iteration collective receive
bytes × executed iterations must equal the runtime comm ledger's
``comm_bytes_total`` exactly on the sharded rungs (the ledger counts
per-device receive rows; SPMD-local HLO shapes are per-device receive
buffers). ``ledger_cross_check`` asserts it — a mismatch means either
the comm accounting or the compiled layout is wrong, which is precisely
the kind of silent bug this telemetry exists to catch.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable, Mapping, Sequence

from repro.launch.hlo_analysis import (
    _CALL_RE,
    _WHILE_RE,
    _WIRE_FACTOR,
    _shape_bytes,
    parse_computations,
    trip_count,
)

#: collective ops counted; ``-done`` halves of async pairs are skipped
_COLL_START_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")

#: replica_groups on this toolchain print as {{0,1,...,7},{...}} (explicit
#: id lists), not the [n,m] iota form hlo_analysis expects — parse both
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(line: str) -> int | None:
    m = _GROUP_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUP_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return None


@dataclass(frozen=True)
class CollectiveOp:
    """One collective in the compiled module, with its loop context."""

    op: str              # "all-reduce" | "all-gather" | ...
    type_str: str        # result type (SPMD-local = per-device receive)
    bytes_per_call: int  # receive bytes per execution of the op
    static_mult: int     # product of statically-recovered trip counts
    depth: int           # dynamic (unknown-trip) while nesting depth
    group_size: int | None


@dataclass
class HloCollectives:
    """All collectives of one compiled executor, by loop context."""

    ops: list[CollectiveOp] = field(default_factory=list)

    def bytes_by_depth(self) -> dict[int, int]:
        """Per-call receive bytes summed per dynamic depth (static loop
        multipliers folded in) — multiply by executed iteration counts
        to get run totals."""
        out: dict[int, int] = {}
        for o in self.ops:
            out[o.depth] = out.get(o.depth, 0) + o.bytes_per_call * o.static_mult
        return out

    def total_bytes(self, iters_by_depth: Mapping[int, int]) -> int:
        """Total per-device receive bytes given the executed iteration
        count of each dynamic loop depth (depth 0 ops run once per
        executor call — pass ``{0: n_calls}`` to count them)."""
        return sum(b * int(iters_by_depth.get(d, 0))
                   for d, b in self.bytes_by_depth().items())

    def wire_bytes(self, iters_by_depth: Mapping[int, int]) -> float:
        """Ring-algorithm wire bytes (hlo_analysis cost model) under the
        same executed-iteration accounting."""
        total = 0.0
        for o in self.ops:
            n = o.group_size or 2
            total += (o.bytes_per_call * o.static_mult
                      * int(iters_by_depth.get(o.depth, 0))
                      * _WIRE_FACTOR[o.op](n))
        return total


def parse_collectives(hlo_text: str) -> HloCollectives:
    """Walk the compiled module from ENTRY, tracking static trip
    multipliers and dynamic while depth, and collect every collective."""
    blocks, entry = parse_computations(hlo_text)
    out = HloCollectives()

    def visit(name: str, static_mult: int, depth: int, seen: tuple):
        if name not in blocks or name in seen:
            return
        lines = blocks[name]
        body = "\n".join(lines)
        for line in lines:
            m = _COLL_START_RE.search(line)
            if not m:
                continue
            if m.group(3) == "-done":
                continue  # async completion — transfer counted at -start
            type_str, op = m.group(1), m.group(2)
            out.ops.append(CollectiveOp(
                op=op, type_str=type_str,
                bytes_per_call=_shape_bytes(type_str),
                static_mult=static_mult, depth=depth,
                group_size=_group_size(line)))
        for cond, wbody in _WHILE_RE.findall(body):
            cond_n, body_n = cond.lstrip("%"), wbody.lstrip("%")
            trips = trip_count(blocks.get(cond_n, []))
            if trips is None:
                # data-dependent trip count (the wave / chunk loops):
                # descend one dynamic depth; the executed count is
                # supplied at accounting time
                visit(body_n, static_mult, depth + 1, seen + (name,))
            else:
                visit(body_n, static_mult * trips, depth, seen + (name,))
        for callee in _CALL_RE.findall(body):
            visit(callee.lstrip("%"), static_mult, depth, seen + (name,))

    visit(entry, 1, 0, ())
    return out


@dataclass
class ExecutorCost:
    """Compiled-cost summary of one jitted engine executor."""

    name: str
    flops: float                 # cost_analysis, loop bodies counted once
    bytes_accessed: float        # same caveat
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    collectives: HloCollectives

    @property
    def peak_bytes(self) -> int:
        """Conservative peak live bytes: arguments + outputs + temps."""
        return self.argument_bytes + self.output_bytes + self.temp_bytes

    def as_row(self, iters_by_depth: Mapping[int, int] | None = None
               ) -> dict:
        """Flat JSON-safe dict for benchmark rows; with iteration counts
        the collective total is resolved, otherwise per-depth per-call
        bytes are recorded for later resolution."""
        row = {
            "executor": self.name,
            "flops": float(self.flops),
            "bytes_accessed": float(self.bytes_accessed),
            "argument_bytes": int(self.argument_bytes),
            "output_bytes": int(self.output_bytes),
            "temp_bytes": int(self.temp_bytes),
            "peak_bytes": int(self.peak_bytes),
            "collective_bytes_by_depth": {
                str(d): int(b)
                for d, b in self.collectives.bytes_by_depth().items()},
        }
        if iters_by_depth is not None:
            row["collective_bytes"] = int(
                self.collectives.total_bytes(iters_by_depth))
        return row


def executor_cost(fn: Callable, *args, name: str = "executor"
                  ) -> ExecutorCost:
    """AOT-lower + compile one jitted executor on example args and
    extract its compiled costs. Lowering never executes, so donated
    argument buffers are untouched."""
    compiled = fn.lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
    except Exception:  # pragma: no cover - backend without memory stats
        mem = None
    return ExecutorCost(
        name=name,
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0) or 0),
        output_bytes=int(getattr(mem, "output_size_in_bytes", 0) or 0),
        temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0) or 0),
        collectives=parse_collectives(compiled.as_text()),
    )


@dataclass(frozen=True)
class CrossCheck:
    """HLO-parsed collective bytes vs the runtime comm ledger."""

    parsed_bytes: int
    ledger_bytes: int
    ratio: float
    ok: bool


def ledger_cross_check(costs: Mapping[str, ExecutorCost] | Sequence[ExecutorCost],
                       iters_by_depth: Mapping[int, int],
                       ledger_bytes: int, *, rtol: float = 0.0
                       ) -> CrossCheck:
    """Check the identity: per-iteration collective receive bytes ×
    executed iterations == the runtime comm ledger's byte total. Exact
    (``rtol=0``) on the sharded rungs — the ledger counts the same
    per-device receive rows the SPMD-local HLO shapes describe."""
    if isinstance(costs, Mapping):
        costs = list(costs.values())
    parsed = sum(c.collectives.total_bytes(iters_by_depth) for c in costs)
    ledger = int(ledger_bytes)
    ratio = parsed / ledger if ledger else (1.0 if not parsed else float("inf"))
    ok = abs(parsed - ledger) <= rtol * max(ledger, 1)
    return CrossCheck(parsed_bytes=int(parsed), ledger_bytes=ledger,
                      ratio=ratio, ok=ok)
