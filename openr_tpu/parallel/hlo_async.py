"""Async-collective evidence for the pipelined blocked closure.

`parallel.blocked.blocked_round_pipelined` fuses round k's rank-B
outer update with round k+1's panel prefetch so the prefetch
collectives carry no data dependence on the outer-update while loop.
On TPU, XLA's AsyncCollectiveCreator + latency-hiding scheduler turn
that independence into `all-gather-start`/`all-gather-done` pairs that
bracket the compute.  The CPU backend never emits the async pair (its
thunk runtime overlaps independent thunks as a dataflow DAG instead),
so "the pairs span the outer update" cannot be grepped out of a CPU
module directly — it has to be PROVED from the module.

This module does exactly that, from the lowered scheduled HLO text and
nothing else:

  * parse the ENTRY computation of a compiled (`is_scheduled=true`)
    module into its instruction list + def-use graph;
  * for every `all-gather`, split it into a start/done pair and
    re-list-schedule the entry with the same legality rule XLA's async
    scheduler uses — an op may sit between start and done iff it is
    neither a transitive producer of the gather's operands nor a
    transitive consumer of its result (checked per span, not assumed);
  * emit the materialized schedule as HLO-shaped text plus a span
    report: which compute ops each start/done pair brackets, whether
    the rank-5 outer-update while is inside, and the collective bytes.

The materialized text is evidence, not an executable: it is the
schedule the async pass is entitled to produce, derived from the real
def-use chains of the real compiled module — "verified from lowered
HLO, not hoped for".
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

#: dtype byte widths for the shapes that appear in the blocked closure
_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1,
    "u8": 1,
    "s16": 2,
    "u16": 2,
    "bf16": 2,
    "f16": 2,
    "s32": 4,
    "u32": 4,
    "f32": 4,
    "s64": 8,
    "u64": 8,
    "f64": 8,
}

_INSTR_RE = re.compile(r"^\s*(?P<root>ROOT\s+)?%(?P<name>[\w.-]+)\s*=\s*(?P<rhs>.*)$")
_OPCODE_RE = re.compile(r"^([a-z][\w-]*)\(")
#: rank-5 u32 per-shard array — the blocked outer update's carry type;
#: no other while in the fused round carries a 5-D operand
_RANK5_U32_RE = re.compile(r"u32\[\d+,\d+,\d+,\d+,\d+\]")
#: the CPU compiler's call wrapper around a small while loop
_WHILE_CALL_RE = re.compile(r"to_apply=%while[\w.-]*_computation\b")


@dataclass
class Instr:
    """One scheduled ENTRY instruction (schedule order == line order
    in a compiled module)."""

    index: int
    name: str
    shape: str
    opcode: str
    operands: list[str]
    attrs: str
    is_root: bool = False
    deps: list[str] = field(default_factory=list)  # operands defined in entry


def _split_shape(rhs: str) -> tuple[str, str]:
    """Split `rhs` into (shape, rest).  Tuple shapes are parenthesized
    and contain no nested parens; array shapes are a single token."""
    if rhs.startswith("("):
        end = rhs.index(")")
        return rhs[: end + 1], rhs[end + 1 :].lstrip()
    parts = rhs.split(" ", 1)
    return parts[0], parts[1] if len(parts) > 1 else ""


def _balanced_args(rest: str, start: int) -> tuple[str, str]:
    """Return (args, attrs) for the operand list opening at
    rest[start] == '('.  Operand lists nest parens only through tuple
    shape annotations, so a depth counter suffices."""
    depth = 0
    for i in range(start, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                return rest[start + 1 : i], rest[i + 1 :].lstrip(", ")
    raise ValueError(f"unbalanced operand list in HLO line: {rest!r}")


def shape_bytes(shape: str) -> int:
    """Total bytes of an array (or tuple) shape string, layouts
    ignored; scalar shapes like `u32[]` count one element."""
    total = 0
    for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", shape):
        width = _DTYPE_BYTES.get(dtype, 4)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * width
    return total


def parse_entry(text: str) -> list[Instr]:
    """Parse the ENTRY computation of a compiled scheduled module into
    schedule-ordered instructions with entry-local def-use edges."""
    header = text.split("\n", 1)[0]
    if "is_scheduled=true" not in header:
        raise ValueError(
            "hlo_async needs a COMPILED module (is_scheduled=true): the "
            "instruction order of an unscheduled module is not a schedule"
        )
    lines = text.splitlines()
    try:
        first = next(i for i, l in enumerate(lines) if l.startswith("ENTRY "))
    except StopIteration:
        raise ValueError("no ENTRY computation in HLO module") from None
    instrs: list[Instr] = []
    for line in lines[first + 1 :]:
        if line.startswith("}"):
            break
        m = _INSTR_RE.match(line)
        if not m:
            continue
        shape, rest = _split_shape(m.group("rhs"))
        op = _OPCODE_RE.match(rest)
        if not op:
            continue
        args, attrs = _balanced_args(rest, op.end() - 1)
        operands = re.findall(r"%([\w.-]+)", args)
        instrs.append(
            Instr(
                index=len(instrs),
                name=m.group("name"),
                shape=shape,
                opcode=op.group(1),
                operands=operands,
                attrs=attrs,
                is_root=bool(m.group("root")),
            )
        )
    known = {i.name for i in instrs}
    for i in instrs:
        i.deps = [o for o in i.operands if o in known]
    return instrs


def _closure(edges: dict[str, list[str]], seeds: list[str]) -> set[str]:
    out: set[str] = set()
    stack = list(seeds)
    while stack:
        n = stack.pop()
        if n in out:
            continue
        out.add(n)
        stack.extend(edges.get(n, ()))
    return out


def _is_loop(i: Instr) -> bool:
    """A while loop of the entry: a `while`, or the `call` the CPU
    compiler wraps a small while into (`to_apply=%while.N_computation`)."""
    return i.opcode == "while" or (
        i.opcode == "call" and _WHILE_CALL_RE.search(i.attrs) is not None
    )


def find_outer_update(instrs: list[Instr]) -> str | None:
    """The round-k outer update: the only while whose carry holds the
    rank-5 u32 tile tensor."""
    for i in instrs:
        if _is_loop(i) and _RANK5_U32_RE.search(i.shape):
            return i.name
    return None


def materialize(text: str) -> tuple[str, list[dict]]:
    """Split every entry `all-gather` into an `all-gather-start` /
    `all-gather-done` pair and re-list-schedule the entry so each done
    sinks to the last legal point (just before its first consumer,
    after every ready independent op).  Returns (materialized entry
    text, span report).

    Starts are hoisted as early as their operands allow, and the
    producers of gather operands are listed ahead of independent work,
    as a latency-hiding scheduler orders them.  Legality is the async
    scheduler's rule, checked per span from the parsed def-use graph: an op between start and done must be neither
    a transitive producer of the gather's operands nor a transitive
    consumer of its result.  The list schedule is a topological order
    by construction, and dones are emitted only when every remaining
    node depends on one — i.e. every gather-independent op (including
    the outer-update while) lands inside every open span."""
    instrs = parse_entry(text)
    by_name = {i.name: i for i in instrs}
    gathers = [i for i in instrs if i.opcode == "all-gather"]

    # node graph with each gather split into start (the gather's deps)
    # and done (the start); users of the gather now consume the done,
    # which keeps every other instruction line textually unchanged
    # priorities, latency-hiding style: starts issue the moment their
    # operands exist, the producers of gather operands run before other
    # work, everything else keeps its compiled order (the compiler's own
    # schedule may put an independent loop ahead of a gather's inputs)
    feeds = _closure({i.name: i.deps for i in instrs}, [
        d for g in gathers for d in g.deps
    ])
    deps: dict[str, list[str]] = {}
    prio: dict[str, tuple[int, int]] = {}
    done_names = {g.name for g in gathers}
    for i in instrs:
        if i.name in done_names:
            deps[i.name + "-start"] = list(i.deps)
            prio[i.name + "-start"] = (0, i.index)
            deps[i.name] = [i.name + "-start"]
            prio[i.name] = (2, i.index)
        else:
            deps[i.name] = list(i.deps)
            prio[i.name] = (1 if i.name in feeds else 2, i.index)

    emitted: set[str] = set()
    order: list[str] = []
    remaining = set(deps)
    while remaining:
        ready = [n for n in remaining if all(d in emitted for d in deps[n])]
        if not ready:
            raise ValueError("cycle in HLO entry def-use graph")
        non_done = [n for n in ready if n not in done_names]
        pick = min(non_done or ready, key=lambda n: prio[n])
        order.append(pick)
        emitted.add(pick)
        remaining.remove(pick)

    # emit text
    users: dict[str, list[str]] = {}
    for i in instrs:
        for d in i.deps:
            users.setdefault(d, []).append(i.name)

    def render(name: str) -> str:
        if name.endswith("-start") and name[:-6] in done_names:
            g = by_name[name[:-6]]
            op_shapes = ", ".join(by_name[o].shape for o in g.deps) or g.shape
            attrs = f", {g.attrs}" if g.attrs else ""
            args = ", ".join(f"{by_name[o].shape} %{o}" for o in g.deps)
            return (
                f"  %{g.name}-start = ({op_shapes}, {g.shape}) "
                f"all-gather-start({args}){attrs}"
            )
        i = by_name[name]
        if name in done_names:
            return (
                f"  %{i.name} = {i.shape} all-gather-done("
                f"(..., {i.shape}) %{i.name}-start)"
            )
        root = "ROOT " if i.is_root else ""
        args = ", ".join(
            f"{by_name[o].shape} %{o}" if o in by_name else f"%{o}"
            for o in i.operands
        )
        attrs = f", {i.attrs}" if i.attrs else ""
        return f"  {root}%{i.name} = {i.shape} {i.opcode}({args}){attrs}"

    pos = {n: k for k, n in enumerate(order)}
    spans: list[dict] = []
    outer = find_outer_update(instrs)
    for g in gathers:
        lo, hi = pos[g.name + "-start"], pos[g.name]
        inside = [n for n in order[lo + 1 : hi] if not n.endswith("-start")]
        # per-span legality check from the def-use graph — not assumed
        # from the scheduler's construction
        producers = _closure(
            {i.name: i.deps for i in instrs}, list(g.deps)
        )
        consumers = _closure(users, users.get(g.name, []))
        illegal = [n for n in inside if n in producers or n in consumers]
        compute = [
            n
            for n in inside
            if by_name.get(n)
            and (_is_loop(by_name[n]) or by_name[n].opcode == "fusion")
        ]
        spans.append(
            {
                "name": g.name,
                "start": lo,
                "done": hi,
                "ops_in_span": inside,
                "compute_in_span": compute,
                "spans_outer_update": outer is not None and outer in inside,
                "legal": not illegal,
                "illegal_ops": illegal,
                "bytes_out": shape_bytes(g.shape),
                "bytes_in": sum(shape_bytes(by_name[o].shape) for o in g.deps),
            }
        )

    body = "\n".join(render(n) for n in order)
    return f"ENTRY %async_materialized {{\n{body}\n}}\n", spans


def async_report(text: str) -> dict:
    """Analyze a compiled pipelined-round module: materialize the async
    spans and summarize the overlap evidence.

    Returns a dict with `spans` (per-gather report from
    `materialize`), `outer_update` (the rank-5 while's name or None),
    `outer_spanning` (how many legal spans bracket the outer update —
    the two PANEL gathers must; the diagonal replication is dep-chained
    through the row-panel gather, so a linear schedule provably cannot
    put the while inside all three), `panel_overlap_ok`
    (outer_spanning >= 2), `collective_bytes` (sum of gathered output
    bytes), and `overlap_frac_est` (percent of entry compute ops —
    whiles and fusions — scheduled inside at least one span)."""
    instrs = parse_entry(text)
    materialized, spans = materialize(text)
    covered: set[str] = set()
    for s in spans:
        covered.update(s["compute_in_span"])
    compute = [
        i.name for i in instrs if _is_loop(i) or i.opcode == "fusion"
    ]
    frac = 100 * len([c for c in compute if c in covered]) // max(len(compute), 1)
    outer_spanning = len(
        [s for s in spans if s["spans_outer_update"] and s["legal"]]
    )
    return {
        "spans": spans,
        "outer_update": find_outer_update(instrs),
        "outer_spanning": outer_spanning,
        "panel_overlap_ok": outer_spanning >= 2,
        "collective_bytes": sum(s["bytes_out"] for s in spans),
        "overlap_frac_est": frac,
        "n_collectives": len(spans),
        "materialized": materialized,
    }
