"""PR 25: the program times itself.

(a) the serve loop's spans land on a live jax trace's host plane, nested
    and numbered, and agree with ``OnlineReport.segment_phases``; the
    gap between two segments (PR 38) runs from one's fetch to the next
    one's launch, and the trace's device idle is read against the spans;
(b) a first token's wait splits into four parts that sum to ``ttft_s``;
(c) every device program, scope and kernel has a stable name, and the
    names are HLO metadata only;
(d) none of it reaches a collector, a token or a journal record that it
    should not.

One file: the traced tests hold jax's one profiler session, so under
``--dist loadfile`` they run one after another in one worker.
"""

import ast
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.scheduler import (TTFT_PARTS, Arrival,
                                            OnlineScheduler,
                                            staggered_arrivals)
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models import llama
from paddle_tpu.parallel import set_mesh
from paddle_tpu.profiler import _hooks, _xplane

PHASES = ("pick", "inputs", "put", "launch", "fetch", "replay",
          "telemetry")
SEGMENT_SCOPES = ("embed", "qkv", "kv_write", "attention", "post", "head",
                  "sample", "segment.admit", "segment.decode")
TRAIN_SCOPES = ("loss", "embed", "qkv", "attention", "post", "head",
                "head_ce", "grad_clip", "optimizer")


@pytest.fixture(scope="module")
def tiny(tiny_llama):
    set_mesh(None)
    return tiny_llama


def paged_engine(cfg, params, slots=4):
    return ServingEngine(cfg, params, slots=slots, max_len=96, paged=True,
                         page_size=8, prompt_buckets=(16,))


def arrivals(cfg, n=6, gap=0.0, gen=5):
    return staggered_arrivals(11, n, gap, cfg.vocab_size,
                              prompt_lens=(6, 12), gen_lens=(gen,))


@contextlib.contextmanager
def jax_trace(log_dir):
    jax.profiler.start_trace(str(log_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_spans(log_dir):
    """Every ``serving.*`` event of the trace's host plane:
    (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(_xplane.latest_xplane(str(log_dir)))
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serving."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def close_to_the_trace(mine, traced, rows):
    """A tally against the trace's own durations (seconds): a span's two
    clock reads sit inside its annotation."""
    return abs(traced - mine) <= 0.05 * traced + 50e-6 * rows


# ---------------------------------------------------------------------------
# (a) spans on the profiler's clock
# ---------------------------------------------------------------------------

class TestSpansInATrace:
    @pytest.fixture(scope="class")
    def traced(self, tiny, tmp_path_factory):
        """One serve under a live jax trace: its report and its
        ``serving.*`` host spans by name, (start, end, stats) each."""
        cfg, params = tiny
        eng = paged_engine(cfg, params)
        sch = OnlineScheduler(eng, seg_steps=4)
        sch.serve(arrivals(cfg))                 # builds the program
        eng.reset_slots()
        sch._reqs.clear()
        log_dir = tmp_path_factory.mktemp("trace")
        with jax_trace(log_dir):
            rep = sch.serve(arrivals(cfg))
        by_name = {}
        for name, s, e, st in host_spans(log_dir):
            by_name.setdefault(name, []).append((s, e, st))
        return rep, by_name

    def test_every_span_nested_numbered_and_summed(self, traced):
        rep, by_name = traced
        want = {"serving.sched.ingest", "serving.segment"} | {
            "serving.segment." + p for p in PHASES}
        assert set(by_name) == want

        segs = {st["seg"]: (s, e, st) for s, e, st in
                by_name["serving.segment"]}
        assert len(segs) == rep.segments
        # its own perf_counter start rides each segment span
        assert all(st["pc_ns"] > 0 for _, _, st in segs.values())
        offs = [s - st["pc_ns"] for s, _, st in segs.values()]
        assert max(offs) - min(offs) < 5e6    # one clock offset, to 5 ms
        for p in PHASES:
            rows = by_name["serving.segment." + p]
            assert len(rows) == rep.segments * (2 if p == "telemetry" else 1)
            for s, e, st in rows:             # inside ITS segment
                ps, pe, _ = segs[st["seg"]]
                assert ps <= s and e <= pe, (p, st)
        # an ingest carries the seg of the segment it precedes
        for s, e, st in by_name["serving.sched.ingest"]:
            if st["seg"] in segs:
                assert e <= segs[st["seg"]][0]
        assert {st["seg"] for _, _, st in
                by_name["serving.sched.ingest"]} >= set(segs)

        # the always-on reduction agrees with the trace's own durations
        total_trace = total_report = 0.0
        for p in ("ingest",) + PHASES:
            rows = by_name["serving.sched.ingest" if p == "ingest"
                           else "serving.segment." + p]
            traced = sum(e - s for s, e, _ in rows) / 1e9
            mine = rep.segment_phases[p]
            assert mine["count"] == len(rows)
            # a span's own two clock reads sit inside its annotation
            assert mine["seconds"] <= traced
            assert close_to_the_trace(mine["seconds"], traced, len(rows)), \
                (p, traced, mine)
            total_trace += traced
            total_report += mine["seconds"]
        assert total_report == pytest.approx(total_trace, rel=0.05)

    def test_put_once_a_segment_inside_its_inputs(self, traced):
        rep, by_name = traced
        inputs = {st["seg"]: (s, e) for s, e, st in
                  by_name["serving.segment.inputs"]}
        puts = by_name["serving.segment.put"]
        assert sorted(st["seg"] for _, _, st in puts) == sorted(inputs)
        for s, e, st in puts:
            lo, hi = inputs[st["seg"]]
            assert lo <= s and e <= hi
        # inside inputs, so segment_host_ms's phases keep their time
        assert rep.segment_phases["put"]["seconds"] <= \
            rep.segment_phases["inputs"]["seconds"]

    def test_gap_from_a_fetch_to_the_next_launch(self, traced):
        """Every segment after the first has a gap (the requests are all
        due at once: the loop never waits for work), from the END of the
        last segment's fetch span to the END of its launch span."""
        rep, by_name = traced
        ends = {}
        for phase in ("fetch", "launch"):
            ends[phase] = {st["seg"]: e for _, e, st in
                           by_name["serving.segment." + phase]}
        segs = sorted(ends["launch"])
        assert len(segs) == rep.segments >= 3
        gaps = [ends["launch"][b] - ends["fetch"][a]
                for a, b in zip(segs, segs[1:])]
        assert all(g > 0 for g in gaps)
        mine = rep.segment_phases["gap"]
        assert mine["count"] == len(gaps) == rep.segments - 1
        assert close_to_the_trace(mine["seconds"], sum(gaps) / 1e9,
                                  len(gaps)), (mine, sum(gaps) / 1e9)
        # the gap is no TraceAnnotation: it reaches collectors only
        assert "serving.segment.gap" not in by_name
        # what fills it: every phase but fetch (and ``put``, inside
        # ``inputs``) lies wholly inside one gap, but for the first
        # segment's dispatch and the last one's replay and telemetry
        windows = [(ends["fetch"][a], ends["launch"][b])
                   for a, b in zip(segs, segs[1:])]
        for names, outside in (
                (("sched.ingest", "segment.pick", "segment.inputs",
                  "segment.launch"), segs[0]),
                (("segment.replay", "segment.telemetry"), segs[-1])):
            for p in names:
                for s, e, st in by_name["serving." + p]:
                    assert st["seg"] == outside or any(
                        lo <= s and e <= hi for lo, hi in windows), (p, st)

    def test_profiler_places_stamped_spans_by_measured_offset(
            self, tiny, tmp_path):
        """``export_chrome_tracing``: a request's lifecycle, stamped on
        perf_counter after the fact, lies inside the serve's segments on
        the trace's clock."""
        import paddle_tpu.profiler as profiler

        cfg, params = tiny
        eng = paged_engine(cfg, params)
        sch = OnlineScheduler(eng, seg_steps=4)
        p = profiler.Profiler(log_dir=str(tmp_path))
        p.start()
        sch.serve(arrivals(cfg, n=3))
        p.stop()
        tables, _ = _xplane.parse(str(tmp_path))
        assert tables["clock_offset_ns"] is not None
        events = profiler.load_profiler_result(p.export_chrome_tracing())
        segs = [e for e in events if e["name"] == "serving.segment"]
        reqs = [e for e in events if e["name"].startswith("request.e2e")]
        assert len(reqs) == 3 and segs
        anchor = [s for s in host_spans(tmp_path)
                  if s[0] == "serving.segment"][0]
        mine = min(segs, key=lambda e: e["ts"])
        # the collector's copy of a span sits on its TraceMe twin
        assert abs(mine["ts"] * 1e3 - anchor[1]) < 2e6
        lo = min(e["ts"] for e in segs)
        hi = max(e["ts"] + e["dur"] for e in segs)
        for e in reqs:
            assert lo - 50e3 <= e["ts"] and e["ts"] + e["dur"] <= hi + 50e3
        # PR 38: each gap (stamped, too) ends at a launch, inside a segment
        gaps = [e for e in events if e["name"] == "serving.segment.gap"]
        assert len(gaps) == len(segs) - 1
        for g in gaps:
            end = g["ts"] + g["dur"]
            assert any(s["ts"] - 2e3 <= end <= s["ts"] + s["dur"] + 2e3
                       for s in segs)


class TestGapChain:
    def test_a_loop_turn_that_waits_for_work_breaks_it(self, tiny):
        """Two requests half a second apart, each served whole in one
        segment: the loop waits for the second, so neither segment has a
        gap (the first of a serve has none)."""
        cfg, params = tiny
        eng = paged_engine(cfg, params, slots=2)
        sch = OnlineScheduler(eng, seg_steps=4)
        sch.serve(arrivals(cfg, n=2, gen=2))      # builds the program
        eng.reset_slots()
        sch._reqs.clear()
        rep = sch.serve(arrivals(cfg, n=2, gap=0.5, gen=2))
        assert rep.segments == 2
        assert "gap" not in rep.segment_phases
        assert eng.gap_from_ns is not None        # the last fetch's end

    def test_each_engine_times_its_own(self, tiny):
        """``run_segment`` back to back outside a serve: a gap a segment
        after the first, closed by the launch; ``abort`` drops the open
        one."""
        cfg, params = tiny
        eng = paged_engine(cfg, params, slots=2)
        rng = np.random.RandomState(0)
        for _ in range(2):
            eng.add_request(rng.randint(0, cfg.vocab_size, (6,))
                            .astype(np.int32), 6)
        for _ in range(3):
            eng.run_segment(2)
        assert eng.segment_phases["serving.segment.gap"][1] == 2
        eng.abort()
        assert eng.gap_from_ns is None

    def test_a_gap_the_profiler_starts_or_stops_in_is_dropped(
            self, tiny, tmp_path):
        """A traced slice opens and closes between two segments: its
        ``start_trace`` / ``stop_trace`` are the profiler's cost, not the
        host's, so those two gaps do not count; the gap inside does."""
        cfg, params = tiny
        eng = paged_engine(cfg, params, slots=2)
        rng = np.random.RandomState(1)
        for _ in range(2):
            eng.add_request(rng.randint(0, cfg.vocab_size, (6,))
                            .astype(np.int32), 12)
        eng.run_segment(2)
        eng.run_segment(2)                       # gap 1
        with jax_trace(tmp_path):
            eng.run_segment(2)                   # dropped: trace started
            eng.run_segment(2)                   # gap 2
        eng.run_segment(2)                       # dropped: trace stopped
        assert eng.segment_phases["serving.segment.gap"][1] == 2


# ---------------------------------------------------------------------------
# (b) the first token's wait in four parts
# ---------------------------------------------------------------------------

class TestFirstTokenSplit:
    def test_admit_step_from_a_hand_built_event_log(self, tiny):
        """2 slots, 6 steps: q0 and q1 admitted at steps 0 and 1, two
        decode ticks (q0, owed 3 tokens, retires at step 3), q2 admitted
        LATE at step 4 into the slot q0 left, one more tick."""
        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(8,))
        prompt = np.arange(4, dtype=np.int32)
        picked = [Request(10, prompt, 3), Request(11, prompt, 9),
                  Request(12, prompt, 9)]
        n_pad = 4
        aq = np.array([0, 1, n_pad, n_pad, 2, n_pad])
        aslot = np.array([0, 1, 0, 0, 0, 0])
        toks = np.arange(12).reshape(6, 2) + 100
        (admitted, first, first_steps, finished, new_tokens,
         _eos) = eng._replay_segment(picked, toks, aq, aslot, 6, 3)
        assert admitted == [10, 11, 12] and first == [10, 11, 12]
        assert first_steps == [0, 1, 4]
        assert finished == [10] and picked[0].tokens == [100, 104, 106]
        assert new_tokens == 3 + 2 + 2 + 1 + 1

    def serve_overpicked(self, cfg, params):
        """2 slots, 5 requests due at once, 3-step segments: every
        segment over-picks (n_pad = 2) while the slots are taken, so some
        requests are picked, find no slot (``qadm < n``) and requeue."""
        eng = paged_engine(cfg, params, slots=2)
        picks = []
        inner = eng._replay_segment

        def replay(picked, *a, **k):
            out = inner(picked, *a, **k)
            picks.append(([r.rid for r in picked], out[0]))
            return out

        eng._replay_segment = replay
        sch = OnlineScheduler(eng, seg_steps=3)
        rep = sch.serve(arrivals(cfg, n=5, gen=4))
        return rep, picks

    def test_parts_sum_to_ttft_with_late_and_requeued(self, tiny):
        cfg, params = tiny
        rep, picks = self.serve_overpicked(cfg, params)
        requeued = {rid for picked, admitted in picks
                    for rid in picked if rid not in admitted}
        assert requeued, "the scenario must requeue a picked request"
        per = {r["rid"]: r for r in rep.per_request}
        for r in per.values():
            assert sum(r[k] for k in TTFT_PARTS) == \
                pytest.approx(r["ttft_s"], abs=1.5e-4)   # ttft_s: 0.1 ms
            assert all(r[k] >= 0 for k in TTFT_PARTS)
            assert 0 <= r["admit_step"] < r["seg_steps"] <= 3
            # split by step index: equal steps inside the segment
            in_seg = r["admit_wait_s"] + r["delivery_wait_s"]
            assert r["admit_wait_s"] == pytest.approx(
                in_seg * (r["admit_step"] + 1) / r["seg_steps"], abs=2e-6)
        assert any(r["admit_step"] > 0 for r in per.values())
        # a requeued request waited through the segment that picked it in
        # vain: its slot wait holds a whole dispatch -> fetch span, while
        # a request admitted at once waits for host work only
        first = [rid for rid in picks[0][1]]
        assert min(per[rid]["slot_wait_s"] for rid in requeued) > \
            10 * max(per[rid]["slot_wait_s"] for rid in first)
        means = rep.ttft_parts_mean_s
        assert sum(means.values()) == pytest.approx(
            sum(r["ttft_s"] for r in per.values()) / len(per), abs=1.5e-4)
        assert rep.as_dict()["ttft_parts_mean_s"] == means

    def test_resumed_request_keeps_its_first_admission(self, tiny):
        """Preempt-and-requeue: the split is stamped where the FIRST token
        was, as ``first_tokens`` is."""
        from paddle_tpu.inference.scheduler import SLOScheduler

        cfg, params = tiny
        eng = paged_engine(cfg, params, slots=1)
        sch = SLOScheduler(eng, seg_steps=3)
        rng = np.random.RandomState(3)
        p = lambda: rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
        rep = sch.serve([Arrival(0.0, p(), 12, priority=1),
                         Arrival(0.05, p(), 3, priority=0)])
        assert rep.preemptions >= 1
        victim = next(r for r in rep.per_request if r["preemptions"])
        assert sum(victim[k] for k in TTFT_PARTS) == \
            pytest.approx(victim["ttft_s"], abs=1.5e-4)
        assert victim["ttft_s"] < victim["e2e_s"]


# ---------------------------------------------------------------------------
# (c) names for every device program, region and kernel
# ---------------------------------------------------------------------------

def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _instructions(hlo_text):
    return len(re.findall(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = ", hlo_text, re.M))


def _has_scope(op_names, scope):
    return any(scope in _xplane.scope_key(n).replace(".bwd", "").split("/")
               for n in op_names)


def lower_paged_segment(cfg, params):
    eng = paged_engine(cfg, params)
    pgr, s_max = eng.pager, eng.buckets[-1]
    i32 = jnp.int32
    return eng._build_paged_segment_prog(4, s_max, 4).lower(
        eng.params, pgr.pool, pgr.page_table, eng._pos, eng._nxt, eng._rem,
        jnp.zeros((4, s_max), i32), jnp.ones((4,), i32),
        jnp.zeros((4,), i32), jnp.zeros((4,), i32),
        jnp.zeros((4, pgr.max_pages), i32), i32(0))


def lower_train_step(cfg, params):
    from paddle_tpu.parallel import create_hybrid_mesh

    mesh = create_hybrid_mesh(devices=jax.devices()[:1])
    set_mesh(mesh)
    try:
        tok = jnp.zeros((2, 16), jnp.int32)
        return llama.make_sharded_train_step(cfg, mesh).lower(
            params, llama.init_opt_state(params), tok, tok)
    finally:
        set_mesh(None)


class TestNames:
    @pytest.mark.parametrize("lower, module, scopes", [
        (lower_paged_segment, "jit_segment", SEGMENT_SCOPES),
        (lower_train_step, "jit_train_step", TRAIN_SCOPES),
    ], ids=["paged_segment", "train_step"])
    def test_program_and_scope_names_are_metadata_only(
            self, tiny, monkeypatch, lower, module, scopes):
        cfg, params = tiny
        lowered = lower(cfg, params)
        assert lowered.as_text().lstrip().startswith(f"module @{module} ")
        text = lowered.compile().as_text()
        names = _op_names(text)
        for scope in scopes:
            assert _has_scope(names, scope), scope
        assert set(scopes) <= set(_xplane.SCOPES)

        # the same program with every scope taken out: same instructions
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = lower(cfg, params).compile().as_text()
        assert not any(_has_scope(_op_names(bare), s) for s in scopes)
        assert _instructions(bare) == _instructions(text) > 0

    def test_every_pallas_call_site_passes_a_name(self):
        here = os.path.dirname(os.path.abspath(llama.__file__))
        files = glob.glob(os.path.join(here, "..", "ops", "pallas", "*.py"))
        sites = []
        for path in files:
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and \
                        getattr(node.func, "attr", "") == "pallas_call":
                    kw = {k.arg: k.value for k in node.keywords}
                    assert "name" in kw, (path, node.lineno)
                    sites.append(kw["name"])
        assert len(sites) == 17
        fixed = sorted(n.value for n in sites if isinstance(n, ast.Constant))
        assert fixed == sorted([
            "flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv", "flash_attention_packed_fwd",
            "flash_attention_packed_bwd", "fused_rms_norm",
            "fused_add_rms_norm", "fused_rope_qk", "quant_matmul",
            "ragged_decode_attention",
            "head_dx_softmax", "mla_paged_attention",
            "grouped_expert_matmul", "power_retention_decode"])
        # two kernels take their name from the caller (a model with two
        # kinds of cache names each call site) and default to their own
        assert sorted(ast.unparse(n) for n in sites
                      if not isinstance(n, ast.Constant)) == \
            ["'apply_flat_update_' + kind", "name", "name"]
        import inspect

        from paddle_tpu.ops.pallas import paged_attention, window_attention

        for fn in (paged_attention.ragged_paged_attention,
                   window_attention.windowed_prefill_attention):
            assert inspect.signature(fn).parameters["name"].default == \
                fn.__name__

    def test_pallas_call_equations_carry_the_name(self):
        """Traced (nothing is lowered, so no chip is needed): the serve
        tick's kernels and the flash pair, by their equations."""
        from paddle_tpu.ops.pallas import (decode_attention,
                                           flash_attention, paged_attention,
                                           tick_fusion)

        def names(fn, *args):
            found = []

            def walk(jaxpr):
                for eqn in jaxpr.eqns:
                    if eqn.primitive.name == "pallas_call":
                        found.append(eqn.params["name"])
                    for v in eqn.params.values():
                        inner = getattr(v, "jaxpr", v)
                        inner = getattr(inner, "jaxpr", inner)
                        if hasattr(inner, "eqns"):
                            walk(inner)

            walk(jax.make_jaxpr(fn)(*args).jaxpr)
            return found

        bf = jnp.bfloat16
        x, w = jnp.ones((8, 128), bf), jnp.ones((128,), bf)
        assert names(lambda a, b: tick_fusion.fused_rms_norm(a, b, 1e-5),
                     x, w) == ["fused_rms_norm"]
        assert names(
            lambda a, b: tick_fusion.fused_add_rms_norm(a, a, b, 1e-5),
            x, w) == ["fused_add_rms_norm"]
        assert names(lambda a: tick_fusion.fused_rope_qk(
            a, a, jnp.zeros((8,), jnp.int32), 64, 1e4), x) == \
            ["fused_rope_qk"]
        q = jnp.ones((2, 1, 4, 64), bf)
        pool = jnp.ones((9, 8, 2 * 64), bf)
        assert names(lambda a, k: paged_attention.ragged_paged_attention(
            a, k, k, jnp.zeros((2, 4), jnp.int32),
            jnp.zeros((2,), jnp.int32)), q, pool) == \
            ["ragged_paged_attention"]
        cache = jnp.ones((2, 128, 2, 64), bf)
        assert names(lambda a, k: decode_attention.ragged_decode_attention(
            a, k, k, jnp.zeros((2,), jnp.int32)), q[:, 0], cache) == \
            ["ragged_decode_attention"]
        qf = jnp.ones((1, 256, 2, 64), bf)
        out, lse = jax.eval_shape(
            lambda a: flash_attention._pallas_flash_fwd_lse(a, a, a, True),
            qf)
        assert names(lambda a: flash_attention._pallas_flash_fwd_lse(
            a, a, a, True), qf) == ["flash_attention_fwd"]
        assert names(lambda a, o, l: flash_attention._pallas_flash_bwd(
            a, a, a, a, o, l, True), qf, jnp.ones(out.shape, out.dtype),
            jnp.ones(lse.shape, lse.dtype)) == \
            ["flash_attention_bwd_dq", "flash_attention_bwd_dkv"]

    def test_scope_key_and_scope_map(self):
        key = _xplane.scope_key
        assert key("jit(segment)/while/body/cond/branch_0_fun/"
                   "segment.decode/while/body/closed_call/qkv/"
                   "dot_general") == "segment.decode/qkv"
        assert key("jit(train_step)/loss/transpose(jvp(post))/jit(silu)/"
                   "mul") == "loss/post.bwd"
        assert key("jit(train_step)/loss/transpose(loss)/jvp(attention)/"
                   "flash_attention_bwd_dq/pallas_call") == \
            "loss/attention.bwd/flash_attention_bwd_dq"
        assert key("params['wq']") == "(no scope)"
        hlo = ('  %dot.2 = f32[2]{0} dot(%a, %b), metadata={op_name='
               '"jit(f)/post/dot_general" source_file="x.py"}\n'
               '  ROOT %fused_rms_norm.1 = bf16[8]{0} custom-call(%x), '
               'metadata={op_name="jit(f)/qkv/fused_rms_norm/pallas_call"}')
        assert _xplane.scope_map(hlo) == {
            "dot.2": "post", "fused_rms_norm.1": "qkv/fused_rms_norm"}

    def test_profiler_summary_prints_the_scope_table(self, tiny, tmp_path,
                                                     capsys):
        """A CPU trace's op events carry no op_name: the scope comes from
        the compiled program's text, through ``summary(scopes=...)``."""
        import paddle_tpu.profiler as profiler

        cfg, params = tiny
        tokens = jnp.zeros((2, 16), jnp.int32)
        fwd = jax.jit(lambda p, t: llama.forward(p, t, cfg))
        compiled = fwd.lower(params, tokens).compile()
        compiled(params, tokens).block_until_ready()
        p = profiler.Profiler(log_dir=str(tmp_path))
        p.start()
        compiled(params, tokens).block_until_ready()
        p.stop()
        scopes = _xplane.scope_map(compiled.as_text())
        assert {"qkv", "post", "head"} <= set(scopes.values())
        tables, _ = _xplane.parse(str(tmp_path), scopes=scopes)
        assert {"qkv", "post"} <= set(tables["scopes"])
        assert sum(v[1] for v in tables["scopes"].values()) == \
            pytest.approx(sum(v[1] for v in tables["kernels"].values()))
        p.summary(scopes=scopes)
        out = capsys.readouterr().out
        assert "Device scope view (named_scope)" in out and "post" in out


# ---------------------------------------------------------------------------
# (d) what the spans must not touch
# ---------------------------------------------------------------------------

class _Collector:
    def __init__(self):
        self.seen = []

    def _host_event(self, name, start_ns, end_ns, kind):
        self.seen.append(name)


class TestSpansTouchNothingElse:
    def test_span_without_trace_or_collector(self):
        assert not _hooks.COLLECTORS
        tally = {}
        with _hooks.span("serving.segment.pick", "serving", tally=tally,
                         seg=7):
            pass
        assert tally["serving.segment.pick"][1] == 1
        c = _Collector()
        _hooks.COLLECTORS.append(c)
        try:
            with _hooks.span("x", "serving", seg=1):
                pass
        finally:
            _hooks.COLLECTORS.remove(c)
        assert c.seen == ["x"]
        with _hooks.span("y", "serving"):     # detached again: not reached
            pass
        assert c.seen == ["x"]

    def test_tokens_and_journal_identical_with_a_trace_live(self, tiny,
                                                            tmp_path):
        """The second serve replays the first one's clock with a jax trace
        AND a collector live: every journal record (decisions, stamps,
        token streams) is the first one's, bit for bit."""
        from paddle_tpu.observability import journal

        cfg, params = tiny

        def serve(clock=None, traced=False):
            eng = paged_engine(cfg, params, slots=2)
            sch = OnlineScheduler(eng, seg_steps=3)
            j = journal.Journal()
            with contextlib.ExitStack() as stack:
                stack.enter_context(journal.attach(j))
                if clock is not None:
                    stack.enter_context(journal.feed_clock(clock))
                if traced:
                    c = _Collector()
                    _hooks.COLLECTORS.append(c)
                    stack.callback(_hooks.COLLECTORS.remove, c)
                    stack.enter_context(jax_trace(tmp_path))
                rep = sch.serve(arrivals(cfg, n=5, gap=0.002, gen=4))
            # "t" is a record's own wall time; cold_start is a flight
            # event that measures wall time since the engine was built
            recs = [{k: v for k, v in r.items() if k != "t"}
                    for r in j.records() if r["kind"] != "cold_start"]
            return rep, sch.results(), recs

        OnlineScheduler(paged_engine(cfg, params, slots=2), seg_steps=3) \
            .serve(arrivals(cfg, n=5, gen=4))     # builds the program
        rep1, toks1, recs1 = serve()
        clock = [r["c"] for r in recs1 if r["kind"] == "clock"]
        rep2, toks2, recs2 = serve(clock, traced=True)
        assert toks1 == toks2
        assert recs1 == recs2
        assert [r["ttft_s"] for r in rep1.per_request] == \
            [r["ttft_s"] for r in rep2.per_request]
        # the split is computed from those same stamps
        assert [[r[k] for k in TTFT_PARTS] for r in rep1.per_request] == \
            [[r[k] for k in TTFT_PARTS] for r in rep2.per_request]
        assert not any("ingest" in str(k) or "slot_wait" in str(k)
                       for r in recs1 for k in r)
        assert len(host_spans(tmp_path)) > 0


# ---------------------------------------------------------------------------
# (e) the device's idle time, by the host span open in it (PR 38)
# ---------------------------------------------------------------------------

class _Ev:
    """An event as the in-tree reader gives it: a name and times, no
    stats."""

    def __init__(self, name, start_ns, end_ns):
        self.name, self.start_ns = name, start_ns
        self.duration_ns = end_ns - start_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Ev(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name = name
        self.lines = [_Line(k, v) for k, v in lines.items()]


# two segments' boundary, ns: segment 0's module ends at 100, the
# inputs' copy runs 130-131, segment 1's module starts at 140
DEVICE = {"XLA Modules": [("jit_segment(1)", 0, 100),
                          ("jit_convert_element_type(2)", 130, 131),
                          ("jit_segment(1)", 140, 240)]}
HOST = {"python": [
    ("serving.segment", 0, 122), ("serving.segment.fetch", 50, 105),
    ("serving.segment.replay", 105, 115),
    ("serving.segment.telemetry", 115, 120),
    ("serving.sched.ingest", 122, 126),         # 126-127: no span
    ("serving.segment", 127, 300), ("serving.segment.pick", 128, 129),
    ("serving.segment.inputs", 129, 135), ("serving.segment.put", 130, 134),
    ("serving.segment.launch", 135, 139), ("serving.segment.fetch", 139, 245),
    ("profiler.clock", 100, 140),               # not the program's
]}
# one plane's idle, by innermost span: [gaps with a part, ns]
IDLE = {"serving.segment.fetch": [2, 6], "serving.segment.replay": [1, 10],
        "serving.segment.telemetry": [1, 5], "serving.segment": [1, 3],
        "serving.sched.ingest": [1, 4], _xplane.NO_SPAN: [1, 1],
        "serving.segment.pick": [1, 1], "serving.segment.inputs": [2, 2],
        "serving.segment.put": [1, 3], "serving.segment.launch": [1, 4]}


def shifted(device, ns):
    return {line: [(n, s + ns, e + ns) for n, s, e in evs]
            for line, evs in device.items()}


@pytest.fixture
def planes():
    return [_Plane("/device:TPU:0", DEVICE), _Plane("/device:TPU:1", DEVICE),
            _Plane("/host:CPU", HOST)]


@pytest.fixture
def hand_built_trace(monkeypatch, tmp_path, planes):
    """A trace directory whose xplane reads as ``planes`` (two device
    planes and the host plane above), through a reader without stats."""

    class Space:
        @classmethod
        def from_file(cls, path):
            return type("S", (), {"planes": planes})()

    (tmp_path / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(_xplane, "_profile_data", lambda: Space)
    return tmp_path


class TestIdleBySpan:
    def test_innermost_span_wins_and_the_rest_is_no_span(self):
        spans = [(s, e, n) for n, s, e in HOST["python"]
                 if n.startswith(_xplane.IDLE_SPANS)]
        modules = [[(s, e) for _, s, e in DEVICE["XLA Modules"]]]
        idle = _xplane.idle_by_span(modules, spans)
        assert idle == IDLE
        assert sum(v[1] for v in idle.values()) == (130 - 100) + (140 - 131)
        # overlapping modules leave no gap; no span at all: all no-span
        assert _xplane.idle_by_span([[(0, 10), (5, 20), (30, 40)]], []) == \
            {_xplane.NO_SPAN: [1, 10]}

    def test_parse_sums_the_device_planes(self, hand_built_trace):
        tables, _ = _xplane.parse(str(hand_built_trace))
        assert tables["idle_offset_ns"] == [0, 0]   # the clocks agree
        assert tables["idle"] == {k: [2 * c, 2 * ns]
                                  for k, (c, ns) in IDLE.items()}

    def test_a_device_clock_that_disagrees_is_shifted_first(
            self, hand_built_trace, planes):
        """Plane 0 reads 6 ns early: its second segment would start
        before the launch that dispatched it began (134 < 135), so it
        is shifted by the least that makes every segment causal (1 ns);
        plane 1 reads 20 ns late: its segments would end after their
        fetches (120 > 105), shifted back by 15."""
        planes[0] = _Plane("/device:TPU:0", shifted(DEVICE, -6))
        planes[1] = _Plane("/device:TPU:1", shifted(DEVICE, 20))
        tables, _ = _xplane.parse(str(hand_built_trace))
        assert tables["idle_offset_ns"] == [1, -15]
        spans = [(s, e, n) for n, s, e in HOST["python"]
                 if n.startswith(_xplane.IDLE_SPANS)]
        want = _xplane.idle_by_span(
            [[(s + d, e + d) for _, s, e in DEVICE["XLA Modules"]]
             for d in (-5, 5)], spans)
        assert tables["idle"] == want
        assert sum(v[1] for v in want.values()) == 2 * 39

    def test_profiler_summary_prints_the_idle_view(self, hand_built_trace,
                                                   capsys):
        import paddle_tpu.profiler as profiler

        profiler.Profiler(log_dir=str(hand_built_trace)).summary()
        out = capsys.readouterr().out
        assert "Device idle by host span" in out
        row = next(ln for ln in out.splitlines()
                   if ln.startswith("serving.segment.replay"))
        assert row.split()[1:3] == ["2", "0.000"]


# ---------------------------------------------------------------------------
# (f) the benchmark's readers of the gap (chipbench/layer_metrics)
# ---------------------------------------------------------------------------

GAP_READERS = ("segment_gap_ms", "segment_gap_ms.saturated")


def gap_reader(name):
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "chipbench", "layer_metrics",
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "lm_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served_record(tiny):
    """A report as the serve kinds record it (``as_dict``)."""
    cfg, params = tiny
    rep = OnlineScheduler(paged_engine(cfg, params), seg_steps=4).serve(
        arrivals(cfg))
    return {"kind": "serve", "report": rep.as_dict(with_requests=True)}


class TestGapReaders:
    @pytest.mark.parametrize("name", GAP_READERS)
    def test_mean_gap_of_the_whole_serve(self, name, served_record):
        gap = served_record["report"]["segment_phases"]["gap"]
        assert gap["count"] == served_record["report"]["segments"] - 1
        mod = gap_reader(name)
        assert mod.compute(served_record) == \
            pytest.approx(gap["seconds"] / gap["count"] * 1e3)
        assert mod.META["layer"] == "engine"
        assert mod.META["moves"] == ("tpot_mean_ms"
                                     if name == "segment_gap_ms"
                                     else "serve_tokens_per_s")

    @pytest.mark.parametrize("name", GAP_READERS)
    def test_none_without_the_gap(self, name, served_record):
        """The parent's report has no ``gap`` (before PR 38)."""
        mod = gap_reader(name)
        report = dict(served_record["report"])
        report["segment_phases"] = {k: v for k, v in
                                    report["segment_phases"].items()
                                    if k not in ("gap", "put")}
        assert mod.compute(dict(served_record, report=report)) is None
        assert mod.compute({"kind": "serve"}) is None
        assert mod.compute({"kind": "train", "report": None}) is None
