"""PR 25: the program times itself.

(a) the serve loop's spans land on a live jax trace's host plane, nested
    and numbered, and agree with ``OnlineReport.segment_phases``;
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

PHASES = ("pick", "inputs", "launch", "fetch", "replay", "telemetry")
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


# ---------------------------------------------------------------------------
# (a) spans on the profiler's clock
# ---------------------------------------------------------------------------

class TestSpansInATrace:
    def test_every_span_nested_numbered_and_summed(self, tiny, tmp_path):
        cfg, params = tiny
        eng = paged_engine(cfg, params)
        sch = OnlineScheduler(eng, seg_steps=4)
        sch.serve(arrivals(cfg))                 # builds the program
        eng.reset_slots()
        sch._reqs.clear()
        with jax_trace(tmp_path):
            rep = sch.serve(arrivals(cfg))
        spans = host_spans(tmp_path)
        by_name = {}
        for name, s, e, st in spans:
            by_name.setdefault(name, []).append((s, e, st))
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
            assert traced - mine["seconds"] <= \
                0.05 * traced + 50e-6 * len(rows), (p, traced, mine)
            total_trace += traced
            total_report += mine["seconds"]
        assert total_report == pytest.approx(total_trace, rel=0.05)

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


class TestPageCounters:
    """PR 31: ``pages_fetched`` / ``page_slots`` of a paged segment — the
    pages its attention calls had to fetch (``pages_read`` at the context
    lengths the host holds) against the page slots they were handed (rows
    x the table's width), a layer."""

    def test_hand_made_segment(self, tiny):
        """2 slots, page 8, table 12 wide, admit width 16: prompts of 6
        and 12 tokens owed 2 and 4; admit, admit, then three ticks of
        which the last two find slot 0 free."""
        from paddle_tpu.ops.pallas.paged_attention import pages_read

        cfg, params = tiny
        eng = paged_engine(cfg, params, slots=2)
        psz, width, s_max = eng.page_size, eng.pager.max_pages, 16
        assert (psz, width) == (8, 12)
        rng = np.random.RandomState(0)
        for n, gen in ((6, 2), (12, 4)):
            eng.add_request(rng.randint(0, cfg.vocab_size, (n,))
                            .astype(np.int32), gen)
        ev = eng.run_segment(8)
        assert ev["steps"] == 5 and len(ev["admitted"]) == 2
        admits = 2 * pages_read(0, s_max, psz)
        ticks = [[(6, True), (12, True)],       # (position, live)
                 [(7, False), (13, True)], [(8, False), (14, True)]]
        fetched = admits + sum(int(pages_read(pos, int(live), psz))
                               for tick in ticks for pos, live in tick)
        assert fetched == 4 + (1 + 2) + 2 + 2
        assert ev["pages_fetched"] == fetched
        assert ev["page_slots"] == 2 * width + 3 * 2 * width
        assert eng.segment_pages == {"pages_fetched": fetched,
                                     "page_slots": ev["page_slots"]}

    def test_report_sums_the_segments(self, tiny):
        from paddle_tpu.observability import metrics

        cfg, params = tiny
        eng = paged_engine(cfg, params, slots=2)
        seen = {"pages_fetched": 0, "page_slots": 0}
        inner = eng.run_segment

        def run_segment(*a, **k):
            ev = inner(*a, **k)
            for name in seen:
                seen[name] += ev[name]
            return ev

        eng.run_segment = run_segment
        before = {n: metrics.counter("serving." + n).value for n in seen}
        rep = OnlineScheduler(eng, seg_steps=3).serve(
            arrivals(cfg, n=5, gen=4))
        assert rep.page_reads == seen == rep.as_dict()["page_reads"]
        assert 0 < seen["pages_fetched"] < seen["page_slots"]
        for n in seen:
            assert metrics.counter("serving." + n).value - before[n] \
                == seen[n]

    def test_chunked_engine_reports_none(self, tiny):
        """A chunked segment's prefill steps are not replayed, so its
        page reads are not reckoned."""
        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=2, max_len=96, page_size=8,
                            prompt_buckets=(16,), chunked_prefill=True)
        rep = OnlineScheduler(eng, seg_steps=6).serve(
            arrivals(cfg, n=2, gen=3))
        assert rep.n_requests == 2 and rep.page_reads is None


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
