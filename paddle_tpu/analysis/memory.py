"""Static HBM liveness auditor (r24, ISSUE 19): peak live bytes per
program, from the optimized HLO alone.

The r9 passes pin syncs/compiles/relayout/donation; r18 meters pool
occupancy at runtime — but nothing statically bounded a program's
**peak live HBM**, the number that actually OOMs a chip. This pass
computes it the way a buffer assigner would, as a deterministic ledger
over the compiled text (``jitted.lower(...).compile().as_text()`` —
the module is ``is_scheduled=true``, so text order IS the instruction
schedule):

* **buffer sizes** come from result shapes (``hlo._shape_bytes``);
* **intervals** are def→last-use over the schedule; entry parameters
  live the whole program (the caller owns their buffers);
* **aliasing is free**: ``tuple`` / ``get-tuple-element`` / ``bitcast``
  / ``optimization-barrier`` / ``copy-done`` produce views, and a
  ``while`` donates its carry through iterations (result aliases the
  operand) — alias results cost 0 bytes and extend their operands'
  lifetimes instead;
* **donation counts once**: ``input_output_alias`` entries zero the
  root operand at the aliased output index — the donated carry (the
  paged pool, optimizer flat state) is billed as its parameter only,
  never as parameter + fresh output;
* **fusion interiors collapse** to the fusion instruction's output
  (interior temporaries live in registers/scratch, not HBM); while
  bodies / conditional branches / calls recurse — their internal peak
  (parameters excluded: they alias caller operands) lands at the call
  site's schedule point;
* **sharded dims divide per-device**: a post-SPMD module
  (``num_partitions=N`` > 1) already carries per-device shapes; for
  un-partitioned text audited against a mesh, per-instruction GSPMD
  ``sharding={devices=[...]}`` annotations divide that buffer, and an
  explicit ``devices=`` divisor covers fully-replicated views.

``peak_live`` returns the per-program ``peak_bytes``, the peak-point
live set (top-N buffers with op/shape/op_name attribution) and a
timeline; ``budgets.Budget.peak_bytes_max`` pins it per canonical
program (cpu-scoped like the other byte ledgers) and ``python -m
paddle_tpu.analysis --gate`` enforces it.

``chip_fit`` joins the liveness result with the §3c weight arithmetic
and the §3f page-pool arithmetic into the **static HBM envelope**
(weights + KV pool + peak transient) — the will-this-replica-fit
surface ``capacity_plan`` embeds and ROADMAP item 3's autoscaler
consumes, cross-validated within ±10% of the r18 PoolMonitor
high-water on a recorded serve (SCALING §3s).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from . import hlo as hlo_passes

__all__ = ["BufferInterval", "MemoryReport", "peak_live", "hot_transients",
           "page_bytes_for", "fixed_part_bytes_for", "pool_bytes_for",
           "transient_estimate",
           "chip_fit", "family_envelopes", "V5E_HBM_BYTES"]

# per-chip HBM capacity the envelope is priced against by default (the
# same v5e datasheet the §3c roofline constants come from: 16 GiB/chip)
V5E_HBM_BYTES = 16 * (1 << 30)


# Ops whose result aliases an existing buffer — zero new bytes; the
# operands' lifetimes extend to the alias's last use instead. ``while``
# is here because XLA threads the carry in place (loop inputs donate
# into outputs); elements the body forwards untouched come back as
# get-tuple-elements and so never double-bill either.
_ALIAS_OPS = frozenset((
    "tuple", "get-tuple-element", "bitcast", "optimization-barrier",
    "copy-done", "while",
))

# Instruction attrs that name computations whose buffers DO occupy HBM
# while the instruction runs (recursed); fusion `calls=` interiors and
# reduce/scatter/sort `to_apply=` scalar combinators are excluded.
_CALLEE_ATTRS = {
    "while": (re.compile(r"body=%?([\w.\-]+)"),
              re.compile(r"condition=%?([\w.\-]+)")),
    "conditional": (re.compile(r"branch_computations=\{([^}]*)\}"),
                    re.compile(r"true_computation=%?([\w.\-]+)"),
                    re.compile(r"false_computation=%?([\w.\-]+)")),
    "call": (re.compile(r"to_apply=%?([\w.\-]+)"),),
}

_DEF_RE = re.compile(
    r"^(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(")
_USE_RE = re.compile(r"%([\w.\-]+)")
_META_RE = re.compile(r",?\s*metadata=\{[^}]*\}")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")
_SHARDING_DEVICES_RE = re.compile(r"sharding=\{[^}]*devices=\[([\d,]+)\]")
_ALIAS_PAIR_RE = re.compile(r"\{\s*(\d*)[\d,\s]*\}:\s*\((\d+)")


@dataclass
class BufferInterval:
    name: str
    op: str
    shape: str
    bytes: int
    start: int
    end: int
    computation: str
    donated: bool = False      # bytes zeroed: aliases a donated param
    param: bool = False        # entry parameter (lives whole program)
    metadata: str = ""         # op_name= source attribution


@dataclass
class MemoryReport:
    program: str
    peak_bytes: int
    peak_index: int
    peak_instruction: str
    param_bytes: int
    donated_param_bytes: int
    transient_bytes: int       # peak_bytes - param_bytes (the working set)
    live_at_peak: List[BufferInterval]
    callee_at_peak: int        # sub-computation contribution at the peak
    timeline: List[Tuple[int, int]]
    num_partitions: int
    devices: int
    schedule_len: int
    intervals: List[BufferInterval] = field(default_factory=list)

    def format(self) -> str:
        mib = 1 / (1 << 20)
        lines = [f"== memory: {self.program} ==",
                 f"  peak {self.peak_bytes * mib:.2f} MiB at "
                 f"#{self.peak_index}/{self.schedule_len} "
                 f"{self.peak_instruction} "
                 f"(params {self.param_bytes * mib:.2f} MiB + transient "
                 f"{self.transient_bytes * mib:.2f} MiB)"]
        for b in self.live_at_peak:
            tag = "param" if b.param else ("donated" if b.donated
                                           else "live")
            lines.append(f"  {tag:>7} {b.bytes * mib:8.3f} MiB {b.name} "
                         f"{b.op} {b.shape}"
                         + (f" [{b.metadata}]" if b.metadata else ""))
        return "\n".join(lines)


def _aliased_output_pairs(hlo_text: str) -> List[Tuple[Optional[int], int]]:
    """[(output tuple index or None for a non-tuple root, param number)]
    from the module's ``input_output_alias`` map."""
    body = hlo_passes._extract_braced(hlo_text, "input_output_alias=")
    if body is None:
        return []
    out = []
    for oi, pnum in _ALIAS_PAIR_RE.findall(body):
        out.append((int(oi) if oi else None, int(pnum)))
    return out


def _sharding_divisor(line: str) -> int:
    """Tile-device product of a per-instruction GSPMD sharding
    annotation (pre-partition modules only). ``last_tile_dim_replicate``
    marks the trailing tile dim as replication, not a shard."""
    m = _SHARDING_DEVICES_RE.search(line)
    if m is None:
        return 1
    dims = [int(d) for d in m.group(1).split(",") if d]
    n = 1
    for d in dims:
        n *= d
    if "last_tile_dim_replicate" in line and dims:
        n //= max(1, dims[-1])
    return max(1, n)


def _parse_instructions(lines, comp_name, entry, divide, shard_aware):
    """One computation's schedule: [(name, op, shape, bytes, raw_line)]
    in text order (= XLA schedule order: the module is is_scheduled)."""
    out = []
    for raw in lines:
        m = _DEF_RE.match(raw)
        if m is None:
            continue
        is_root, name, shape_text, op = (bool(m.group(1)), m.group(2),
                                         m.group(3), m.group(4))
        if op in _ALIAS_OPS:
            nbytes = 0
        elif op == "parameter" and not entry:
            nbytes = 0          # aliases the caller's operand buffer
        else:
            nbytes = hlo_passes._shape_bytes(shape_text)
            div = divide * (_sharding_divisor(raw) if shard_aware else 1)
            if div > 1:
                nbytes = -(-nbytes // div)
        out.append((name, op, shape_text, nbytes, raw, is_root))
    return out


def _comp_peak(comp_name: str, comps: Dict[str, list], fused: set,
               divide: int, shard_aware: bool, memo: Dict[str, int],
               stack: set) -> int:
    """Internal peak of a non-entry computation (params billed 0: they
    alias caller operands, already live at the call site)."""
    if comp_name in memo:
        return memo[comp_name]
    if comp_name not in comps or comp_name in stack:
        return 0
    stack = stack | {comp_name}
    instrs = _parse_instructions(comps[comp_name], comp_name, False,
                                 divide, shard_aware)
    peak, _idx, _live, _callee = _liveness(instrs, comp_name, comps,
                                           fused, divide, shard_aware,
                                           memo, stack, entry=False)
    memo[comp_name] = peak
    return peak


def _callees(op: str, raw: str, fused: set) -> List[str]:
    pats = _CALLEE_ATTRS.get(op)
    if not pats:
        return []
    names: List[str] = []
    for pat in pats:
        m = pat.search(raw)
        if not m:
            continue
        for tok in m.group(1).split(","):
            tok = tok.strip().lstrip("%")
            if tok and tok not in fused:
                names.append(tok)
    return names


def _liveness(instrs, comp_name, comps, fused, divide, shard_aware,
              memo, stack, entry, alias_pairs=()):
    """Sweep one computation's schedule; returns (peak, peak_idx,
    intervals, callee_peak_at_idx)."""
    n = len(instrs)
    if n == 0:
        return 0, 0, [], {}
    index = {name: i for i, (name, *_r) in enumerate(instrs)}
    last_use = {name: i for name, *_r in instrs
                for i in (index[name],)}
    # last textual use of each value (metadata stripped so quoted
    # op_name paths can't fake a reference; % prefix required)
    for i, (_name, _op, _shape, _b, raw, _root) in enumerate(instrs):
        rhs = _META_RE.sub("", raw.split("=", 1)[1] if "=" in raw else raw)
        for u in _USE_RE.findall(rhs):
            if u in index and index[u] < i:
                last_use[u] = max(last_use[u], i)
    # alias results extend their operands' lifetimes (reverse order
    # resolves chains: gte(while(tuple(x))) pins x to the gte's end)
    for i in range(n - 1, -1, -1):
        name, op, _shape, _b, raw, _root = instrs[i]
        if op not in _ALIAS_OPS:
            continue
        rhs = _META_RE.sub("", raw.split("=", 1)[1])
        for u in set(_USE_RE.findall(rhs)):
            if u in index and index[u] < i:
                last_use[u] = max(last_use[u], last_use[name])

    root_i = next((i for i in range(n - 1, -1, -1) if instrs[i][5]), n - 1)
    root_name, root_op = instrs[root_i][0], instrs[root_i][1]
    last_use[root_name] = n - 1

    # donated outputs: the root operand at an aliased output index
    # reuses the parameter's buffer — bill it 0 (counted once, as the
    # parameter). Applies to the entry computation only.
    donated_ops: set = set()
    if entry and alias_pairs:
        rhs = _META_RE.sub("", instrs[root_i][4].split("=", 1)[1])
        root_operands = [u for u in _USE_RE.findall(rhs) if u in index]
        for out_idx, _pnum in alias_pairs:
            if out_idx is None and root_op != "tuple":
                donated_ops.add(root_name)
            elif root_op == "tuple" and out_idx is not None \
                    and out_idx < len(root_operands):
                donated_ops.add(root_operands[out_idx])

    intervals: List[BufferInterval] = []
    delta = [0] * (n + 1)
    meta = {}
    for i, (name, op, shape, nbytes, raw, _root) in enumerate(instrs):
        is_param = entry and op == "parameter"
        donated = name in donated_ops and not is_param
        billed = 0 if donated else nbytes
        start = 0 if is_param else i
        end = (n - 1) if is_param else max(i, last_use.get(name, i))
        m = _OPNAME_RE.search(raw)
        meta[name] = m.group(1) if m else ""
        if billed or is_param or donated:
            intervals.append(BufferInterval(
                name=name, op=op, shape=shape, bytes=billed, start=start,
                end=end, computation=comp_name, donated=donated,
                param=is_param, metadata=meta[name]))
        delta[start] += billed
        delta[end + 1] -= billed

    callee_peak = {}
    for i, (_name, op, _shape, _b, raw, _root) in enumerate(instrs):
        names = _callees(op, raw, fused)
        if names:
            callee_peak[i] = max(
                _comp_peak(c, comps, fused, divide, shard_aware, memo,
                           stack) for c in names)

    peak = peak_idx = 0
    live = 0
    for i in range(n):
        live += delta[i]
        total = live + callee_peak.get(i, 0)
        if total > peak:
            peak, peak_idx = total, i
    return peak, peak_idx, intervals, callee_peak


def peak_live(hlo_text: str, *, program: str = "program",
              devices: int = 1, top_n: int = 8,
              timeline_points: int = 128) -> MemoryReport:
    """Liveness sweep over an optimized HLO module's entry schedule.

    ``devices`` divides EVERY buffer — the per-device view of a
    replicated (un-partitioned) module lowered for a ``devices``-wide
    mesh. A post-SPMD module (``num_partitions`` > 1 in the header)
    already carries per-device shapes, so leave ``devices=1`` there;
    per-instruction ``sharding=`` annotations additionally divide
    their own buffer in un-partitioned text.
    """
    header = hlo_text.split("\n", 1)[0]
    m = _NUM_PARTITIONS_RE.search(header)
    num_partitions = int(m.group(1)) if m else 1
    shard_aware = num_partitions <= 1
    comps = {}
    entry_name, entry_lines = None, []
    for name, is_entry, lines in hlo_passes._computations(hlo_text):
        comps[name] = lines
        if is_entry:
            entry_name, entry_lines = name, lines
    fused = hlo_passes._fusion_computations(hlo_text)
    fused |= {c for c in comps if "fused_computation" in c}
    alias_pairs = _aliased_output_pairs(hlo_text)
    instrs = _parse_instructions(entry_lines, entry_name or "entry",
                                 True, devices, shard_aware)
    memo: Dict[str, int] = {}
    peak, peak_idx, intervals, callee_peak = _liveness(
        instrs, entry_name or "entry", comps, fused, devices,
        shard_aware, memo, {entry_name or "entry"}, entry=True,
        alias_pairs=alias_pairs)

    param_bytes = sum(b.bytes for b in intervals if b.param)
    donated_param_bytes = sum(
        p.bytes for p in hlo_passes.entry_parameters(hlo_text)
        if p.aliased)
    if devices > 1:
        donated_param_bytes = -(-donated_param_bytes // devices)

    live_at_peak = sorted(
        (b for b in intervals if b.start <= peak_idx <= b.end
         and (b.bytes or b.donated)),
        key=lambda b: -b.bytes)[:top_n]
    peak_instr = instrs[peak_idx][0] if instrs else ""

    # decimated live-bytes timeline (callee contributions included)
    n = len(instrs)
    stride = max(1, n // max(1, timeline_points))
    delta = [0] * (n + 1)
    for b in intervals:
        delta[b.start] += b.bytes
        delta[b.end + 1] -= b.bytes
    timeline, live = [], 0
    for i in range(n):
        live += delta[i]
        if i % stride == 0 or i == peak_idx:
            timeline.append((i, live + callee_peak.get(i, 0)))

    return MemoryReport(
        program=program, peak_bytes=peak, peak_index=peak_idx,
        peak_instruction=peak_instr, param_bytes=param_bytes,
        donated_param_bytes=donated_param_bytes,
        transient_bytes=max(0, peak - param_bytes),
        live_at_peak=live_at_peak,
        callee_at_peak=callee_peak.get(peak_idx, 0),
        timeline=timeline, num_partitions=num_partitions,
        devices=devices, schedule_len=n, intervals=intervals)


def hot_transients(report: MemoryReport, *, frac_bytes: float = 0.33,
                   frac_span: float = 0.6) -> List[BufferInterval]:
    """Non-parameter buffers that dominate the peak AND stay live
    across most of the schedule — the logits_all-across-steps class: a
    per-step value accumulated whole instead of reduced. These are the
    liveness blowups a peak-budget regression usually decomposes into.
    The span is counted in the schedule's computing positions: a
    ``parameter`` or ``constant`` does no work, and a lowering that
    hoists a few of them ahead of the buffer's birth would otherwise
    dilute a buffer that outlives the whole loop below the bar.
    """
    idle = [b.start for b in report.intervals
            if b.op in ("parameter", "constant")]
    n = max(1, report.schedule_len - len(idle))

    def span(b):
        return (b.end - b.start + 1) - sum(b.start <= i <= b.end
                                           for i in idle)

    return [b for b in report.intervals
            if not b.param and not b.donated
            and b.bytes >= frac_bytes * max(1, report.peak_bytes)
            and span(b) >= frac_span * n]


# ---------------------------------------------------------------------------
# The static HBM envelope: weights + KV pool + peak transient (§3s)
# ---------------------------------------------------------------------------


def page_bytes_for(cfg, page_size: int, quant: Optional[str] = None) -> int:
    """Bytes one pool page occupies across all layers — the §3f page
    arithmetic, byte-priced. The row layout is the model's: the K + V
    planes of ``llama`` (+ the scale planes under per-page quantization),
    the one latent plane of ``latent_moe``, a state of ``power_retention``,
    the full layers' K + V rows of ``hybrid_moe`` (whose window layers keep
    a fixed part a sequence instead: ``fixed_part_bytes_for``)."""
    from ..models import family_of

    return family_of(cfg).page_bytes(cfg, page_size, quant)


def fixed_part_bytes_for(cfg) -> int:
    """Bytes of one sequence's FIXED part — what it keeps whatever its
    length beside its pages (the window layers' last rows of
    ``hybrid_moe``); 0 for a model whose module declares none."""
    from ..models import family_of

    return getattr(family_of(cfg), "fixed_part_bytes", lambda _: 0)(cfg)


def pool_bytes_for(cfg, num_pages: int, page_size: int,
                   quant: Optional[str] = None, fixed_parts: int = 0) -> int:
    """Provisioned pool bytes (``init_paged_pool`` arithmetic): every
    page is allocated up front, including the trash page, and so is every
    fixed part (``fixed_parts``: the trash part among them)."""
    return num_pages * page_bytes_for(cfg, page_size, quant) \
        + fixed_parts * fixed_part_bytes_for(cfg)


def transient_estimate(cfg, *, n_pad: int, s_max: int,
                       tokens_per_tick: int = 1) -> int:
    """Analytic peak-transient model for one serving tick/admit wave:
    the fp32 logits block (× tokens_per_tick — a verify tick or a
    ``logits_all`` program holds one per emitted position) plus a
    working set of hidden-width activations over the admit window.
    Validated against the measured liveness transient of the canonical
    gate programs (tests/test_memory_analysis.py) — an ESTIMATE for
    sizing real replicas, not a budget; budgets pin the measured pass.
    """
    import jax.numpy as jnp

    itemsize = jnp.dtype(cfg.dtype).itemsize
    logits = n_pad * tokens_per_tick * cfg.vocab_size * 4
    hidden = 6 * n_pad * s_max * cfg.hidden_size * itemsize
    scores = n_pad * cfg.num_heads * s_max * s_max * itemsize
    return int(logits + hidden + scores)


def chip_fit(cfg=None, params=None, *, pool=None, page_size=None,
             num_pages=None, quant=None, mesh_devices: int = 1,
             hbm_bytes: int, weights_bytes: Optional[int] = None,
             transient_bytes: Optional[int] = None,
             n_pad: Optional[int] = None, s_max: Optional[int] = None,
             live_pages: Optional[int] = None,
             trace_stats: Optional[dict] = None,
             program_family: str = "pseg") -> dict:
    """Static will-this-replica-fit: the §3s HBM envelope.

    ``envelope_bytes = weights + provisioned KV pool + peak transient``
    — all three per-device (weights and the pool shard over
    ``mesh_devices`` on the kv-head/output dims). ``pool`` may be a
    live ``PagedKVCache`` (its planes are summed exactly) or pool
    geometry (``page_size``/``num_pages``). The live-KV prediction
    (``kv_live_bytes``) prices the §3f span arithmetic at high-water —
    the term cross-validated ±10% against the r18 PoolMonitor on a
    recorded serve.
    """
    if weights_bytes is None:
        import jax

        weights_bytes = sum(
            int(x.size) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(params))
    weights_bytes = -(-int(weights_bytes) // max(1, mesh_devices))

    if pool is not None:
        pool_b = sum(int(v.size) * v.dtype.itemsize
                     for v in pool.pool.values())
        pool_b += int(pool.page_table.size) * pool.page_table.dtype.itemsize
        page_size = pool.page_size
        num_pages = pool.num_pages
        page_b = page_bytes_for(cfg, page_size, quant)
    else:
        page_b = page_bytes_for(cfg, page_size, quant)
        pool_b = num_pages * page_b
    pool_b = -(-pool_b // max(1, mesh_devices))
    page_b = -(-page_b // max(1, mesh_devices))

    if transient_bytes is None:
        transient_bytes = transient_estimate(
            cfg, n_pad=n_pad if n_pad is not None else 4,
            s_max=s_max if s_max is not None else 4 * (page_size or 16))
    transient_bytes = int(transient_bytes)

    if live_pages is None and trace_stats is not None:
        S = float(trace_stats["mean_prompt_tokens"])
        G = float(trace_stats["mean_new_tokens"])
        span = max(1, math.ceil((S + G - 1) / page_size))
        conc = float(trace_stats.get("concurrency",
                                     trace_stats.get("slots", 1)))
        live_pages = int(math.ceil(conc * span))
    kv_live_bytes = (live_pages * page_b if live_pages is not None
                     else None)

    envelope = weights_bytes + pool_b + transient_bytes
    headroom = hbm_bytes - envelope
    return {
        "arithmetic": "SCALING §3s static HBM envelope: weights + "
                      "provisioned pool + peak transient",
        "program_family": program_family,
        "mesh_devices": int(mesh_devices),
        "hbm_bytes": int(hbm_bytes),
        "weights_bytes": int(weights_bytes),
        "pool_bytes": int(pool_b),
        "page_bytes": int(page_b),
        "num_pages": int(num_pages) if num_pages else None,
        "transient_bytes": transient_bytes,
        "envelope_bytes": int(envelope),
        "fits": bool(envelope <= hbm_bytes),
        "headroom_bytes": int(headroom),
        "headroom_pages": int(headroom // page_b) if headroom > 0 else 0,
        "utilization": round(envelope / hbm_bytes, 4),
        "predicted_high_water_pages": live_pages,
        "kv_live_bytes": (int(kv_live_bytes)
                          if kv_live_bytes is not None else None),
    }


def family_envelopes(engine, envelope, *, hbm_bytes: Optional[int] = None,
                     mesh_devices: int = 1) -> Dict[str, dict]:
    """Per-family static envelopes over the engine's declared program
    space: for every family the workload envelope reaches, price its
    WIDEST enumerated key (max admit width × window) through the §3s
    arithmetic. The autoscaler's per-family chip-fit table — weights
    and pool are shared; only the transient differs per family."""
    from ..inference.program_space import PROGRAM_SPACE

    by_fam = PROGRAM_SPACE.enumerate_by_family(engine, envelope)
    cfg = engine.cfg
    pager = getattr(engine, "pager", None)
    out: Dict[str, dict] = {}
    for fam_name, keys in sorted(by_fam.items()):
        if not keys:
            continue
        # enumerate_by_family returns a set of key tuples; order it so
        # the widest-key tie-break is deterministic across runs
        keys = sorted(keys, key=repr)
        fam = PROGRAM_SPACE.family(fam_name)
        widest_pad, widest_span, widest_tok = 1, 1, 1
        widest_key = keys[0]
        for key in keys:
            kw = dict(zip(fam.axes, key[1:]))
            n_pad = int(kw.get("n_pad", getattr(engine, "slots", 1)) or 1)
            span = max(int(kw.get(a, 0) or 0)
                       for a in ("s_max", "C", "chunk", "width")) or 1
            tok = int(kw.get("K", 0) or 0) + 1
            if n_pad * span * tok >= widest_pad * widest_span * widest_tok:
                widest_pad, widest_span, widest_tok = n_pad, span, tok
                widest_key = key
        transient = transient_estimate(cfg, n_pad=widest_pad,
                                       s_max=widest_span,
                                       tokens_per_tick=widest_tok)
        entry = {"keys": len(keys), "widest_key": widest_key,
                 "transient_bytes": transient,
                 "budget_program": fam.budget_program}
        if hbm_bytes is not None and pager is not None:
            entry["fit"] = chip_fit(
                cfg, engine.params, page_size=pager.page_size,
                num_pages=pager.num_pages,
                quant=getattr(engine, "quant", None),
                mesh_devices=mesh_devices, hbm_bytes=hbm_bytes,
                transient_bytes=transient, program_family=fam_name)
        out[fam_name] = entry
    return out
