"""Compiled-HLO collective accounting for the tests.

The only multi-chip perf evidence a single-host rig can produce:
compile the partitioned program on a virtual CPU mesh, walk the HLO,
and pin communication volume to theory. Used by
``tests/unit/test_hlo_collectives.py`` / ``test_hlo_quantized_comm.py``.

Counting rules:

- **Elements** are backend-invariant for float math comparisons (the
  CPU backend upcasts bf16 dots to f32, so float byte counts are not).
- **Bytes** ARE meaningful for quantized payloads: int8 collectives
  stay s8 in HLO on every backend (FloatNormalization touches only
  floats), which is exactly what the quantized-comm audits measure.
- all-reduce counts 2x its size (ring cost = reduce-scatter +
  all-gather); all-to-all / all-gather / reduce-scatter /
  collective-permute count 1x their output.
- async pairs count ONCE: the ``-start`` form is skipped (its tuple
  result carries operand + result, double-counting the transfer) and
  the ``-done`` form's plain result is counted.
"""

import re
from typing import List, NamedTuple, Optional, Tuple

__all__ = ["HLO_DTYPE_BYTES", "shape_elems", "shape_bytes",
           "Collective", "collect_collectives", "collect_collectives_full",
           "wire_elements", "wire_bytes_of", "send_bytes_of",
           "conditional_branch_comps", "hlo_computation_body",
           "dense_allreduce_ring_bytes", "while_body_comps",
           "cone_reaches_compute", "overlap_structure",
           "gather_ops", "max_gather_elems"]

# dtype name -> byte width; accounting by ELEMENTS uses only the names
HLO_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8,
                   "f32": 4, "s32": 4, "u32": 4,
                   "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                   "s8": 1, "u8": 1, "pred": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def _shapes(shape_str):
    """[(dtype, elems)] for every array in an HLO result type (handles
    tuples)."""
    out = []
    for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", shape_str):
        if dt not in HLO_DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((dt, n))
    return out


def shape_elems(shape_str) -> int:
    """Total elements across every array in an HLO result type."""
    return sum(n for _, n in _shapes(shape_str))


def shape_bytes(shape_str) -> int:
    """Total payload bytes across every array in an HLO result type."""
    return sum(n * HLO_DTYPE_BYTES[dt] for dt, n in _shapes(shape_str))


def _group_size(line) -> Optional[int]:
    """Devices per replica group of a collective instruction, parsed from
    either the explicit ``replica_groups={{0,1},{2,3}}`` form or the
    iota ``replica_groups=[G,S]<=[...]`` form (S = group size). None if
    the attribute is absent (single-group collective)."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]*)\}", line)
    if m:
        ids = [x for x in m.group(1).split(",") if x]
        return len(ids)
    return None


class Collective(NamedTuple):
    op: str            # e.g. "all-gather"
    elems: int         # result elements (transfer size, counting rules)
    bytes: int         # result payload bytes (int8-aware)
    group_size: Optional[int]  # devices per replica group
    line: str
    comp: Optional[str]        # enclosing HLO computation name


def _iter_collectives(hlo_text):
    comp = None
    comp_pat = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^=]*\)\s*->")
    # the result type may be a variadic tuple whose long form carries
    # /*index=N*/ comments (which contain '='), so match lazily up to
    # the op name rather than forbidding '=' inside parens
    pat = re.compile(
        r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (\(.*?\)|\S+) "
        r"(" + "|".join(_COLLECTIVES) + r")(-start|-done)?\(",
    )
    for line in hlo_text.splitlines():
        cm = comp_pat.match(line)
        if cm and "{" in line:
            comp = cm.group(1)
        m = pat.match(line)
        if m:
            if m.group(3) == "-start":
                continue            # counted at the matching -done
            yield m, line, comp


def collect_collectives(hlo_text):
    """[(op, result_elems, line, computation)] for every collective
    instruction in a compiled (SPMD-partitioned) HLO module — the
    4-tuple shape the element-count audits consume."""
    return [(m.group(2), shape_elems(m.group(1)), line.strip(), comp)
            for m, line, comp in _iter_collectives(hlo_text)]


def collect_collectives_full(hlo_text) -> List[Collective]:
    """:class:`Collective` records with byte accounting and replica-group
    sizes — what the quantized-comm audits need (int8 payloads, and
    which mesh axis a collective ran over, identified by group size)."""
    out = []
    for m, line, comp in _iter_collectives(hlo_text):
        shape = m.group(1)
        # async -done: replica_groups live on the matching -start line
        gsz = _group_size(line)
        out.append(Collective(op=m.group(2), elems=shape_elems(shape),
                              bytes=shape_bytes(shape), group_size=gsz,
                              line=line.strip(), comp=comp))
    if any(c.group_size is None and c.line.find("-done(") >= 0
           for c in out):
        # map -done ops to their -start's replica_groups via operand name
        starts = {}
        for raw in hlo_text.splitlines():
            sm = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (?:\(.*?\)|\S+) "
                          r"(?:" + "|".join(_COLLECTIVES) + r")-start\(",
                          raw)
            if sm:
                starts[sm.group(1)] = _group_size(raw)
        fixed = []
        for c in out:
            if c.group_size is None:
                dm = re.search(r"-done\(%?([\w.\-]+)\)", c.line)
                if dm and dm.group(1) in starts:
                    c = c._replace(group_size=starts[dm.group(1)])
            fixed.append(c)
        out = fixed
    return out


def wire_elements(colls) -> int:
    """Ring-model wire cost in elements: all-reduce = 2x its size.
    Accepts 4-tuples or :class:`Collective` records."""
    return sum(c[1] * (2 if c[0] == "all-reduce" else 1) for c in colls)


def wire_bytes_of(colls) -> int:
    """Ring-model wire cost in result-payload bytes (int8-aware);
    requires :class:`Collective` records."""
    return sum(c.bytes * (2 if c.op == "all-reduce" else 1) for c in colls)


def dense_allreduce_ring_bytes(n: int, world: int,
                               dtype_bytes: int = 2) -> int:
    """Theory baseline: per-rank bytes of a dense ring allreduce of
    ``n`` elements (reduce-scatter + all-gather legs)."""
    return 2 * (world - 1) * n * dtype_bytes // world


def send_bytes_of(colls, default_group: Optional[int] = None) -> int:
    """Per-rank SEND volume in bytes: result-payload bytes converted by
    each collective's replica-group size. An all-gather / all-to-all
    result of ``n`` bytes over a group of ``g`` means each rank sent
    (and received) ``(g-1)/g * n`` — its own chunk never crossed the
    wire; an all-reduce ring costs 2x that. This is the convention the
    host-side wire model (``quantized_collectives.wire_bytes``) reports,
    so model-vs-HLO drift checks compare like for like instead of
    carrying the W/(W-1) fudge factor around. ``default_group`` covers
    collectives with no replica_groups attribute (single whole-world
    group)."""
    total = 0.0
    for c in colls:
        g = c.group_size or default_group
        f = (g - 1) / g if g and g > 1 else 1.0
        total += c.bytes * f * (2 if c.op == "all-reduce" else 1)
    return int(round(total))


_GATHER_PAT = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (\(.*?\)|\S+) gather\(")


def gather_ops(hlo_text) -> List[Tuple[int, int, str]]:
    """[(result_elems, result_bytes, result_shape)] for every ``gather``
    instruction in a compiled HLO module. The paged-serving bandwidth
    audits use this to pin WHERE decode reads come from: the
    stripe-gather decode path materializes a gather of every table
    entry's page per layer (a ``max_len``-bounded tensor), while the
    fused Pallas decode kernel's program contains no pool-sized gather
    at all — its pool reads are per-page dynamic slices."""
    out = []
    for line in hlo_text.splitlines():
        m = _GATHER_PAT.match(line)
        if m:
            shape = m.group(1)
            out.append((shape_elems(shape), shape_bytes(shape),
                        shape.strip("()")))
    return out


def max_gather_elems(hlo_text) -> int:
    """Largest single gather result (elements) in a compiled module;
    0 when the program contains no gather."""
    return max((e for e, _, _ in gather_ops(hlo_text)), default=0)


def while_body_comps(hlo_text):
    """Names of computations used as while-loop bodies (lax.scan /
    fori_loop lower to these)."""
    return {m.group(1)
            for m in re.finditer(r"\bbody=%?([\w.\-]+)", hlo_text)}


_DEF_PAT = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
# compute markers: a dot-general, a convolution, or a backend matmul
# custom-call (the CPU backend may rewrite dots to oneDNN custom-calls)
_COMPUTE_PAT = re.compile(r"\b(?:dot|convolution)\(|__onednn|\$matmul|"
                          r"custom-call.*gemm", re.IGNORECASE)
_CALLS_PAT = re.compile(r"(?:calls|to_apply|body|condition|"
                        r"true_computation|false_computation)="
                        r"%?([\w.\-]+)")


def _body_defs(hlo_text, comp_name):
    """{instr name: line} for one computation's body."""
    defs = {}
    for line in hlo_computation_body(hlo_text, comp_name):
        m = _DEF_PAT.match(line)
        if m:
            defs[m.group(1)] = line
    return defs


def _comp_has_compute(hlo_text, comp_name, _memo=None):
    """True if a computation (or anything it calls) contains a
    dot/convolution/matmul instruction."""
    if _memo is None:
        _memo = {}
    if comp_name in _memo:
        return _memo[comp_name]
    _memo[comp_name] = False          # cycle guard
    hit = False
    for line in hlo_computation_body(hlo_text, comp_name):
        if _COMPUTE_PAT.search(line):
            hit = True
            break
        for cm in _CALLS_PAT.finditer(line):
            if _comp_has_compute(hlo_text, cm.group(1), _memo):
                hit = True
                break
        if hit:
            break
    _memo[comp_name] = hit
    return hit


def _line_operands(line):
    """Names referenced after the '=' of an instruction line (operands
    plus called-computation attrs — the cone walk filters by the body's
    def map, and inspects called computations separately)."""
    eq = line.find(" = ")
    return re.findall(r"%([\w.\-]+)", line[eq + 3:] if eq >= 0 else line)


def _cone_walk(hlo_text, defs, root_names, memo):
    """BFS over the operand cone of ``root_names`` within one body's
    ``defs`` map; True when it reaches compute (directly or inside a
    called computation). ``memo`` caches per-computation compute
    lookups across walks — overlap_structure shares one across every
    collective it audits."""
    seen = set()
    frontier = []
    for r in root_names:
        frontier.extend(o for o in _line_operands(defs[r]) if o in defs)
    while frontier:
        name = frontier.pop()
        if name in seen:
            continue
        seen.add(name)
        line = defs[name]
        if _COMPUTE_PAT.search(line):
            return True
        for cm in _CALLS_PAT.finditer(line):
            if _comp_has_compute(hlo_text, cm.group(1), memo):
                return True
        frontier.extend(o for o in _line_operands(line) if o in defs)
    return False


def cone_reaches_compute(hlo_text, comp_name, root_pred):
    """Dependence audit for compute/comm overlap: does the operand cone
    of any instruction matching ``root_pred`` (a predicate on the raw
    line) inside computation ``comp_name`` reach a dot-general /
    convolution / matmul — transitively through operands, and through
    fusion/call bodies?

    A SERIAL exchange consumes gradients produced by the same
    iteration's backward, so its cone contains dot-generals. An
    OVERLAPPED (double-buffered) exchange consumes only the loop carry
    — its cone is dot-free, which is exactly the structural fact that
    lets the scheduler run it concurrently with the next micro-step's
    compute. Scheduler- and backend-independent, unlike textual
    instruction order."""
    defs = _body_defs(hlo_text, comp_name)
    roots = [name for name, line in defs.items() if root_pred(line)]
    return _cone_walk(hlo_text, defs, roots, {})


def overlap_structure(hlo_text, payload_pred=lambda line: "s8[" in line):
    """Structural overlap report of a compiled fused-step program, for
    the tier-1 overlap audits.

    Looks at every while-loop body that contains both compute
    (dot-general/matmul) and collectives whose line matches
    ``payload_pred`` (default: int8 payloads — the quantized exchange),
    and reports::

        exchange_collectives   total matching collectives in loop bodies
        overlap_free           how many have a dot-free operand cone
                               (structurally overlappable with the
                               iteration's compute)
        overlap_fraction       overlap_free / exchange_collectives
        interleaved_fraction   fraction positioned between the first
                               and last dot-general in the printed body
                               (schedule-order view; serial ~ tail)
        flush_outside_loop     matching collectives OUTSIDE loop bodies
                               (the post-scan flush of the last window)
    """
    bodies = while_body_comps(hlo_text)
    total = free = 0
    interleaved = 0
    in_body_names = set()
    memo = {}          # shared per-computation compute cache
    for comp in bodies:
        defs = _body_defs(hlo_text, comp)
        in_body_names |= set(defs)
        lines = list(defs.items())
        coll = [(i, name) for i, (name, line) in enumerate(lines)
                if any(op + "(" in line for op in _COLLECTIVES)
                and payload_pred(line)]
        dots = [i for i, (_, line) in enumerate(lines)
                if _COMPUTE_PAT.search(line)]
        if not coll or not dots:
            continue
        total += len(coll)
        lo, hi = min(dots), max(dots)
        interleaved += sum(1 for i, _ in coll if lo < i < hi)
        for _, name in coll:
            if not _cone_walk(hlo_text, defs, [name], memo):
                free += 1
    outside = 0
    for c in collect_collectives_full(hlo_text):
        if payload_pred(c.line):
            m = _DEF_PAT.match(c.line)
            name = m.group(1) if m else None
            if name not in in_body_names:
                outside += 1
    return {
        "exchange_collectives": total,
        "overlap_free": free,
        "overlap_fraction": (free / total) if total else 0.0,
        "interleaved_fraction": (interleaved / total) if total else 0.0,
        "flush_outside_loop": outside,
    }


def conditional_branch_comps(hlo_text):
    """Names of computations used as lax.cond branches (direct bodies)."""
    names = set()
    for m in re.finditer(r"(?:true_computation|false_computation)="
                         r"%?([\w.\-]+)", hlo_text):
        names.add(m.group(1))
    for m in re.finditer(r"branch_computations=\{([^}]*)\}", hlo_text):
        for n in m.group(1).split(","):
            names.add(n.strip().lstrip("%"))
    return names


def hlo_computation_body(hlo_text, comp_name):
    """Lines of one named HLO computation's body."""
    lines = hlo_text.splitlines()
    out, inside = [], False
    pat = re.compile(r"^\s*(?:ENTRY\s+)?%?" + re.escape(comp_name) +
                     r"\s*\(")
    for line in lines:
        if not inside and pat.match(line) and "{" in line:
            inside = True
            continue
        if inside:
            if line.strip() == "}" or line.strip().startswith("}"):
                break
            out.append(line)
    return out
