"""Loop-aware roofline analysis of compiled (SPMD-partitioned) HLO.

XLA's ``compiled.cost_analysis()`` visits every while body ONCE, so a model
scanned over L layers under-counts FLOPs/bytes/collectives by ~L× (verified
in this repo — see EXPERIMENTS.md §Roofline methodology). This module parses
the post-optimization HLO text instead:

  1. split into computations; per computation collect
       - dot/convolution FLOPs (2 · prod(out shape) · prod(contracting dims))
       - dot operand+output bytes (HBM-traffic proxy: weights/activations
         streamed per matmul — the dominant memory term for LM workloads)
       - collective bytes by op kind (per-device output-shape bytes)
  2. build the call graph (while bodies, calls, conditionals, fusions)
  3. walk from ENTRY multiplying by while trip counts (parsed from the loop
     condition's comparison constant; dynamic ``while_loop``s get 1× and are
     flagged)

Terms (per chip, peaks from ``PEAKS`` by ``device_kind``): compute = FLOPs
/ peak FLOP/s, memory = bytes / HBM bandwidth, collective = bytes / link
bandwidth per link (all-reduce counted 2×: reduce-scatter + all-gather
phases). All quantities are per-device (the compiled module is the
per-device program), so terms are directly per-chip seconds.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

V5E = "TPU v5 lite"        # jax.Device.device_kind of a TPU v5e chip

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (4 links of
# 50 GB/s).
PEAKS = {
    V5E: dict(flops=197e12, hbm_bw=819e9, link_bw=50e9, hbm_bytes=16e9),
}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; unknown kinds are an error
    (a default would silently price another chip as a v5e)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to roofline.PEAKS "
                         f"with their source") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_AR_FACTOR = 2.0           # ring AR = reduce-scatter + all-gather

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(text: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    for line in text.splitlines():
        # computation header: `%name (params...) -> type {` — params may nest
        # parens (tuple-typed), so match greedily up to `-> ... {`
        m = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$",
                     line)
        if m and ("=" not in line.split("(")[0]):
            cur = m.group(1)
            comps[cur] = []
            continue
        if line.strip() in ("}", "})"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps


def _dims_list(attr: str, line: str) -> list[int]:
    m = re.search(attr + r"=\{([0-9,]*)\}", line)
    if not m or not m.group(1):
        return []
    return [int(x) for x in m.group(1).split(",")]


@dataclasses.dataclass
class CompStats:
    dot_flops: float = 0.0
    dot_bytes: float = 0.0
    coll_bytes: dict[str, float] = dataclasses.field(default_factory=dict)
    coll_count: dict[str, int] = dataclasses.field(default_factory=dict)
    whiles: list[tuple[str, str, int | None]] = dataclasses.field(
        default_factory=list)
    calls: list[str] = dataclasses.field(default_factory=list)
    const_ints: list[int] = dataclasses.field(default_factory=list)
    dynamic_while: bool = False


def _analyze_computation(lines: list[str]) -> CompStats:
    st = CompStats()
    # symbol table: value name -> type string (ops define one value per line;
    # operands are printed as bare %names in optimized HLO)
    types: dict[str, str] = {}
    parsed: list[tuple[str, str, str, str]] = []  # (name, opcode, type, rhs)
    for line in lines:
        m = _OP_RE.match(line)
        if not m:
            for c in re.findall(r"constant\((\d+)\)", line):
                st.const_ints.append(int(c))
            continue
        name, rhs = m.group(1), m.group(2)
        for c in re.findall(r"constant\((\d+)\)", rhs):
            st.const_ints.append(int(c))
        op_m = re.search(r"(?:^|\)\s|\]\s|\}\s)\s*([a-z][a-z0-9\-]*)\(", rhs)
        opcode = op_m.group(1) if op_m else ""
        type_str = rhs.split(opcode + "(", 1)[0] if opcode else rhs
        types[name] = type_str
        parsed.append((name, opcode, type_str, rhs))

    def operand_names(rhs: str, opcode: str) -> list[str]:
        m = re.search(re.escape(opcode) + r"\(([^)]*)\)", rhs)
        if not m:
            return []
        return [o.strip().lstrip("%") for o in m.group(1).split(",")
                if o.strip()]

    def dims_of(name: str) -> list[int]:
        t = types.get(name)
        if not t:
            return []
        sm = _SHAPE_RE.search(t)
        if not sm:
            return []
        return [int(x) for x in sm.group(2).split(",") if x]

    for name, opcode, type_str, rhs in parsed:
        if opcode == "dot":
            out_elems = 1
            for d in dims_of(name):
                out_elems *= d
            ops = operand_names(rhs, "dot")
            contract = _dims_list("lhs_contracting_dims", rhs)
            c_elems = 1
            if ops:
                lhs_dims = dims_of(ops[0])
                for ci in contract:
                    if ci < len(lhs_dims):
                        c_elems *= lhs_dims[ci]
            st.dot_flops += 2.0 * out_elems * c_elems
            st.dot_bytes += _shape_bytes(type_str) + sum(
                _shape_bytes(types.get(o, "")) for o in ops[:2])
        elif opcode == "convolution":
            out_dims = dims_of(name)
            ops = operand_names(rhs, "convolution")
            out_elems = 1
            for d in out_dims:
                out_elems *= d
            ker = 1
            for d in (dims_of(ops[1]) if len(ops) > 1 else []):
                ker *= d
            st.dot_flops += 2.0 * out_elems * max(ker, 1) / max(
                out_dims[-1] if out_dims else 1, 1)
            st.dot_bytes += _shape_bytes(type_str) + sum(
                _shape_bytes(types.get(o, "")) for o in ops[:2])
        elif opcode == "while":
            cm = re.search(r"condition=%?([\w.\-]+)", rhs)
            bm = re.search(r"body=%?([\w.\-]+)", rhs)
            tm = re.search(r'known_trip_count[^}]*"n":"(\d+)"', rhs)
            if cm and bm:
                st.whiles.append((cm.group(1), bm.group(1),
                                  int(tm.group(1)) if tm else None))
        elif opcode in ("call", "fusion", "custom-call", "async-start"):
            for cal in re.findall(r"(?:to_apply|calls)=%?([\w.\-]+)", rhs):
                st.calls.append(cal)
        elif opcode == "conditional":
            for grp in re.findall(r"branch_computations=\{([^}]+)\}", rhs):
                for c in grp.split(","):
                    st.calls.append(c.strip().lstrip("%"))
            for cal in re.findall(
                    r"(?:true_computation|false_computation)=%?([\w.\-]+)",
                    rhs):
                st.calls.append(cal)
        else:
            base = None
            for cname in _COLLECTIVES:
                if opcode and opcode.startswith(cname):
                    base = cname
                    break
            if base and not (opcode or "").endswith("-done"):
                # wire-bytes basis per kind: AG counts received (output),
                # RS/A2A/permute count sent (operand), AR counts operand
                # (x2 applied later: ring AR = RS + AG phases)
                out_b = _shape_bytes(type_str)
                ops = operand_names(rhs, opcode or "")
                in_b = sum(_shape_bytes(types.get(o, "")) for o in ops)
                b = out_b if base == "all-gather" else max(in_b, out_b) \
                    if base == "all-reduce" else (in_b or out_b)
                st.coll_bytes[base] = st.coll_bytes.get(base, 0.0) + b
                st.coll_count[base] = st.coll_count.get(base, 0) + 1
    return st


def analyze_hlo(text: str) -> dict[str, Any]:
    """Loop-aware totals over the whole module (per-device quantities)."""
    comps = {name: _analyze_computation(lines)
             for name, lines in _split_computations(text).items()}
    entry = None
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.MULTILINE)
    if m:
        entry = m.group(1)
    if entry is None or entry not in comps:  # fall back: largest computation
        entry = max(comps, key=lambda k: len(comps[k].calls) + 1,
                    default=None)

    totals = dict(dot_flops=0.0, dot_bytes=0.0, coll_bytes={}, coll_count={},
                  dynamic_whiles=0, while_trips=[])

    def trip_count(cond_name: str) -> int | None:
        st = comps.get(cond_name)
        if st is None or not st.const_ints:
            return None
        return max(st.const_ints)

    seen_stack: list[str] = []

    def walk(name: str, mult: float):
        st = comps.get(name)
        if st is None or name in seen_stack:
            return
        seen_stack.append(name)
        totals["dot_flops"] += st.dot_flops * mult
        totals["dot_bytes"] += st.dot_bytes * mult
        for k, v in st.coll_bytes.items():
            totals["coll_bytes"][k] = totals["coll_bytes"].get(k, 0.0) + v * mult
        for k, v in st.coll_count.items():
            totals["coll_count"][k] = totals["coll_count"].get(k, 0) \
                + int(v * mult)
        for c in st.calls:
            walk(c, mult)
        for cond, body, trip in st.whiles:
            t = trip if trip is not None else trip_count(cond)
            if t is None:
                totals["dynamic_whiles"] += 1
                t = 1
            totals["while_trips"].append(t)
            walk(body, mult * t)
            walk(cond, mult * t)
        seen_stack.pop()

    if entry:
        walk(entry, 1.0)
    return totals


def roofline_terms(analysis: dict[str, Any], *, n_links: int = 4,
                   device_kind: str = V5E) -> dict:
    """Three per-chip roofline terms (seconds) from analyze_hlo output."""
    pk = peaks(device_kind)
    coll = analysis["coll_bytes"]
    coll_eff = sum(v * (_AR_FACTOR if k == "all-reduce" else 1.0)
                   for k, v in coll.items())
    compute_s = analysis["dot_flops"] / pk["flops"]
    memory_s = analysis["dot_bytes"] / pk["hbm_bw"]
    collective_s = coll_eff / (pk["link_bw"] * n_links)
    terms = dict(compute_s=compute_s, memory_s=memory_s,
                 collective_s=collective_s,
                 collective_bytes=coll_eff, flops=analysis["dot_flops"],
                 hbm_bytes=analysis["dot_bytes"])
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    terms["bottleneck"] = dom.replace("_s", "")
    total = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
    terms["roofline_fraction"] = terms["compute_s"] / total if total else 0.0
    return terms


def coloring_memory_projection(n_global: int, P: int, maxd: int, *,
                               maxd2: int = 0, ghost_frac: float = 0.5,
                               boundary_frac: float = 0.5,
                               batch: int = 1,
                               device_kind: str = V5E) -> dict:
    """Per-shard device bytes of the coloring layout under the id policy.

    Projects the ``PartitionedGraph.arrays()`` footprint for a graph of
    ``n_global`` vertices block-partitioned over ``P`` shards at max degree
    ``maxd`` (``maxd2`` adds the distance-2 ELL halo) — *without*
    allocating anything, so the int64 giant-graph regime (RMAT scale
    30+) can be sized on paper.  Id widths come from
    ``core.graph.id_policy``: the per-shard slot arrays (ELL neighbours,
    CSR columns, boundary/ghost tables) stay int32 at any global size —
    they index slots, not global ids — so promotion past the 2**31 vertex
    bound only widens the id-carrying arrays (``prio``/``gvid``) and the
    gather-index temporaries, and the projection makes that visible as
    ``promoted_extra_bytes``.

    ``ghost_frac``/``boundary_frac`` model the halo as a fraction of the
    local block (0.5 matches the repo's RMAT partitions at CPU scale;
    structured meshes sit far lower).  ``batch`` multiplies the working
    views (the batched pipeline holds one view per lane).  Returns the
    per-array byte dict plus totals and the HBM occupancy fraction of one
    ``device_kind`` chip.
    """
    import numpy as np                       # lazy: keep roofline import-light

    from repro.core.graph import id_policy

    n_local = -(-n_global // P)
    pol = id_policy(n_global, n_local, maxd, maxd2)
    n_ghost = int(n_local * ghost_frac)
    n_boundary = int(n_local * boundary_frac)
    n_slots = n_local + n_ghost + 1
    m_local = n_local * maxd
    id_b = pol.id_itemsize
    lanes = max(batch, 1)
    per = dict(
        nbr=n_local * maxd * 4,             # ELL neighbour slots: int32
        nbr2=n_local * maxd2 * 4,           # distance-2 ELL halo
        indices=m_local * 4,                # CSR column slots: int32
        edge_src=m_local * 4,
        indptr=(n_local + 1) * 4,
        prio=n_slots * id_b,                # global priorities: id-width
        gvid=n_slots * id_b,                # global-id map: id-width
        boundary=n_boundary * 4,
        ghost_tables=2 * n_ghost * 4,       # ghost_owner + ghost_slot
        degree_flags=n_local * 5,           # degree (int32) + is_internal
        views=n_slots * 4 * lanes,          # working color views per lane
    )
    total = sum(per.values())
    hbm = peaks(device_kind)["hbm_bytes"]
    # what the same layout would cost if ids stayed int32 (the gap is the
    # whole price of the int64 promotion)
    extra = (n_slots * (id_b - 4)) * 2 if pol.promoted else 0
    return dict(
        n_global=int(n_global), P=int(P), n_local_max=int(n_local),
        maxd=int(maxd), maxd2=int(maxd2), batch=lanes,
        id_dtype=np.dtype(pol.id_dtype).name,
        ell_dtype=np.dtype(pol.ell_dtype).name,
        promoted=pol.promoted, promoted_extra_bytes=int(extra),
        per_shard_bytes=per, total_per_shard=int(total),
        hbm_fraction=total / hbm, fits_hbm=total <= hbm)


def model_flops(arch, shape) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active params."""
    n = arch.n_active_params() if arch.is_moe else arch.n_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n * tokens
