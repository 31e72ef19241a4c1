"""The power-retention decoder (``models/power_retention.py``) against its
plain reference (``tests/reference_power_retention.py``: the attention form,
no state), at a small size on the CPU: ``phi``; the chunked form against the
recurrence; prefill then ticks through state pages, on LOGITS; the decode
kernel interpreted against the fallback; what a state page asks of
``forward_with_pages`` — (a) position 0 starts from zero, (b) a bucket's
padding leaves the state alone, (c) dead slots touch only the trash page,
(d) the planes are held once; page reuse; the state fork; planted faults;
the engine end to end; scopes and counters; the refusals.

Tolerances: everything here is float32, and the program sums in another
order than the reference (chunks and a carried state against one [T, T]
matrix), so logits of magnitude ~3 agree to ~1e-5; ``TOL`` leaves a factor
of ten and is a hundred times under what any planted fault moves.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_power_retention as ref
from paddle_tpu.inference.paged_kv import PagedKVCache
from paddle_tpu.inference.scheduler import Arrival, OnlineScheduler
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import family_of, llama, power_retention as pr
from paddle_tpu.ops.pallas import power_retention as op
from paddle_tpu.parallel import set_mesh
from paddle_tpu.profiler import _hooks

TOL = dict(rtol=2e-4, atol=2e-4)
MAX_LEN = 64            # = page_size: a page is a sequence
WIDTH = 16              # the admit bucket


def sizes(cfg):
    """The config as the public config.json's keys (what the reference
    reads)."""
    return {"num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "num_hidden_layers": cfg.num_layers,
            "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta}


def jiggle(params, seed=3):
    """Norm scales away from 1 and gate biases apart (about 2, a decay of
    ~0.88 a token: a dropped decay shows within a dozen rows), so that a
    dropped norm or a gate read from the wrong head shows."""
    rng = np.random.RandomState(seed)
    lay = dict(params["layers"])
    for name in ("n1", "n2", "nq", "nk"):
        a = lay[name]
        lay[name] = a * (1.0 + 0.3 * rng.standard_normal(a.shape)).astype(
            a.dtype)
    lay["bg"] = jnp.asarray(2.0 + rng.uniform(
        -1.0, 1.0, lay["bg"].shape).astype(np.float32))
    return dict(params, layers=lay,
                ln_f=params["ln_f"] * (1.0 + 0.3 * rng.standard_normal(
                    params["ln_f"].shape)).astype(params["ln_f"].dtype))


_JITS = {}


def ref_logits(params, tokens, m, faults=(), pad_to=32):
    """``ref.logits`` under one jit a fault set; the sequence is padded
    (causal: what follows a position changes nothing before it)."""
    key = (tuple(faults), pad_to)
    if key not in _JITS:
        _JITS[key] = jax.jit(lambda p, t: ref.logits(p, t, m, faults))
    seq = np.zeros((pad_to,), np.int32)
    seq[:len(tokens)] = np.asarray(tokens)
    return np.asarray(_JITS[key](params, jnp.asarray(seq)))[:len(tokens)]


@pytest.fixture(scope="module")
def tiny():
    set_mesh(None)
    cfg = pr.PowerRetentionConfig.tiny()
    params = jiggle(pr.init_params(cfg, jax.random.PRNGKey(1)))
    return cfg, params


@contextlib.contextmanager
def kernel_interpreted(on=True):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(op, "FORCE_INTERPRET", on)
        yield


def forward_fn(cfg, params):
    """A jit of its own a call: the kernel's dispatch is read while
    tracing."""
    def forward(tokens, pool, table, pos, live=None, logit_pos=None,
                counters=False):
        return pr.forward_with_pages(params, tokens, cfg, pool, table, pos,
                                     live=live, logit_pos=logit_pos,
                                     with_counters=counters)

    return jax.jit(forward, static_argnames=("counters",))


PROMPTS = [np.random.RandomState(s).randint(0, 256, (n,)).astype(np.int32)
           for s, n in [(0, 11), (1, 16), (2, 5)]]


def admit(forward, pool, page, prompt, pos=0):
    """One admission as the engine's admit branch makes it: the prompt
    padded to the bucket, ``logit_pos`` its last row."""
    row = np.zeros((1, WIDTH), np.int32)
    row[0, :len(prompt)] = prompt
    return forward(jnp.asarray(row), pool, jnp.asarray([[page]], jnp.int32),
                   jnp.asarray([pos], jnp.int32),
                   logit_pos=jnp.int32(len(prompt) - 1))


def paged_run(cfg, params, prompts, n_decode, pool=None):
    """Admit ``prompts`` one a slot (slot b's state is page b + 1), then
    ``n_decode`` ticks over all slots fed the tokens the program picked.
    Returns {slot: [logits at each fed position]}, the tokens fed and the
    pool."""
    B = len(prompts)
    forward = forward_fn(cfg, params)
    if pool is None:
        pool = pr.init_paged_pool(cfg, B + 1, MAX_LEN)
    table = 1 + np.arange(B, dtype=np.int32).reshape(B, 1)
    full = [np.concatenate([p, np.zeros(n_decode, np.int32)])
            for p in prompts]
    got = {b: [] for b in range(B)}
    for b, p in enumerate(prompts):
        logits, pool = admit(forward, pool, b + 1, p)
        got[b].append(np.asarray(logits[0]))
        full[b][len(p)] = int(np.argmax(logits[0]))
    pos = np.array([len(p) for p in prompts], np.int32)
    for step in range(n_decode):
        tok = np.array([[full[b][pos[b]]] for b in range(B)], np.int32)
        logits, pool = forward(jnp.asarray(tok), pool, jnp.asarray(table),
                               jnp.asarray(pos))
        for b in range(B):
            got[b].append(np.asarray(logits[b]))
            if step + 1 < n_decode:
                full[b][pos[b] + 1] = int(np.argmax(logits[b]))
        pos = pos + 1
    return got, full, pool


def check_against_reference(cfg, params, got, full, prompts):
    m = sizes(cfg)
    for b, p in enumerate(prompts):
        want = ref_logits(params, full[b], m)
        for i, row in enumerate(got[b]):
            np.testing.assert_allclose(row, want[len(p) - 1 + i], **TOL)


# phi and the two forms -----------------------------------------------------

@pytest.mark.parametrize("d", [32, 128])
def test_phi_is_exact(d):
    """``phi(q) . phi(k) == (q . k)^2`` to float32 rounding, at the width
    ``state_width`` names (9,216 = 72 x 128 at d 128)."""
    q = jax.random.normal(jax.random.PRNGKey(2), (7, d))
    k = jax.random.normal(jax.random.PRNGKey(3), (7, d))
    got = (op.phi(q) * op.phi(k)).sum(-1)
    want = (q * k).sum(-1) ** 2
    assert op.phi(q).shape == (7, op.state_width(d))
    assert op.state_width(128) == 9216 and op.state_width(32) == 768
    # a sum of D float32 products of both signs against one square: an
    # error of a few float32 roundings of the terms' size, |q|^2 |k|^2
    scale = float(((q * q).sum(-1) * (k * k).sum(-1)).max())
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * scale)


def _rows(seed, B, T, kv=2, group=2, d=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, T, kv, group, d)) / np.sqrt(d)
    k = jax.random.normal(ks[1], (B, T, kv, d))
    v = jax.random.normal(ks[2], (B, T, kv, d))
    g = -jax.random.uniform(ks[3], (B, T, kv)) * 0.3
    return q, k, v, g


def test_chunked_form_matches_the_recurrence():
    """Chunks of 8 over 24 rows from a state that is not zero == the same
    rows one tick at a time through the fallback (the recurrence as
    written)."""
    B, T, kv, d = 2, 24, 2, 32
    q, k, v, g = _rows(5, B, T)
    D = op.state_width(d)
    s0 = jax.random.normal(jax.random.PRNGKey(6), (B, kv, D, d))
    z0 = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (B, kv, D))) + 1
    y, s1, z1 = op.power_retention_chunked(q, k, v, g, s0, z0, chunk=8)
    s = jnp.zeros((1, B + 1, kv, D, d)).at[0, 1:].set(s0)
    z = jnp.zeros((1, B + 1, kv, D)).at[0, 1:].set(z0)
    page = jnp.arange(1, B + 1)
    live, fresh = jnp.ones((B,), bool), jnp.zeros((B,), bool)
    for t in range(T):
        yt, s, z = op.power_retention_decode(
            q[:, t], k[:, t], v[:, t], g[:, t], s, z, page, live, fresh)
        np.testing.assert_allclose(yt, y[:, t], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s[0, 1:], s1, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(z[0, 1:], z1, rtol=2e-4, atol=2e-4)


def test_prefill_then_ticks_through_pages_match_reference(tiny):
    """The chunked admission (two chunks of 8 a bucket), then six ticks a
    slot through the state pages, against the reference's full forward of
    the whole sequence, on logits."""
    cfg, params = tiny
    got, full, _ = paged_run(cfg, params, PROMPTS, 6)
    check_against_reference(cfg, params, got, full, PROMPTS)


# the kernel ----------------------------------------------------------------

@pytest.mark.parametrize("live", [(1, 1, 1, 1), (0, 1, 0, 1), (1, 0, 0, 0),
                                  (0, 0, 0, 0)])
def test_kernel_interpreted_matches_fallback(live):
    """``power_retention_decode`` interpreted == the gather / update /
    scatter fallback: outputs, the pages of the live slots, and EVERY
    other page untouched (a dead slot moves nothing); a traced layer of a
    two-layer plane, one slot fresh over a page that holds garbage."""
    B, kv, group, d = 4, 2, 2, 32
    D = op.state_width(d)
    q, k, v, g = (a[:, 0] for a in _rows(8, B, 1))
    s = jax.random.normal(jax.random.PRNGKey(9), (2, B + 2, kv, D, d))
    z = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (2, B + 2, kv, D)))
    page = jnp.asarray([3, 1, 5, 2], jnp.int32)
    live = jnp.asarray(live, bool)
    fresh = jnp.asarray([False, True, False, False])

    def step():     # a function of its own a jit: the dispatch is traced
        return jax.jit(lambda s, z, lay: op.power_retention_decode(
            q, k, v, g, s, z, page, live, fresh, layer=lay))

    want = step()(s, z, jnp.int32(1))
    op.reset_selection_count()
    with kernel_interpreted():
        got = step()(s, z, jnp.int32(1))
    assert op.selection_count() == 1
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    held = np.asarray(page)[np.asarray(live)]
    np.testing.assert_allclose(got[1][1, held], want[1][1, held], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got[2][1, held], want[2][1, held], rtol=1e-5,
                               atol=1e-5)
    others = np.setdiff1d(np.arange(1, B + 2), held)
    np.testing.assert_array_equal(got[1][1, others], s[1, others])
    np.testing.assert_array_equal(got[1][0], s[0])      # the other layer
    np.testing.assert_array_equal(got[1][1, 0], s[1, 0])  # the trash page


def test_kernel_through_the_model_matches_reference(tiny):
    cfg, params = tiny
    op.reset_selection_count()
    with kernel_interpreted():
        assert pr.paged_kernel_active(cfg, MAX_LEN)
        got, full, _ = paged_run(cfg, params, PROMPTS, 6)
    assert op.selection_count() >= 1
    check_against_reference(cfg, params, got, full, PROMPTS)
    assert not pr.paged_kernel_active(cfg, MAX_LEN)


# what a state page asks of forward_with_pages ---------------------------------

def _garbage(cfg, pages, fill=3.0):
    pool = pr.init_paged_pool(cfg, pages, MAX_LEN)
    return {n: a + fill + jnp.arange(pages, dtype=a.dtype).reshape(
        (1, pages) + (1,) * (a.ndim - 2)) for n, a in pool.items()}


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("rows", [1, WIDTH])
def test_position_zero_starts_from_a_zero_state(tiny, kernel, rows):
    """(a) at ``pos == 0`` what the page held is ignored — a tick (one
    row) and an admission alike, finite garbage and NaN alike (0 x NaN is
    NaN: the old state is selected away, not scaled) — and at ``pos > 0``
    it is continued."""
    cfg, params = tiny
    tokens = jnp.asarray(PROMPTS[1][None, :rows])
    table = jnp.asarray([[2]], jnp.int32)
    with kernel_interpreted(kernel):
        forward = forward_fn(cfg, params)
        zero = jnp.zeros((1,), jnp.int32)
        clean, p1 = forward(tokens, pr.init_paged_pool(cfg, 4, MAX_LEN),
                            table, zero)
        dirty, p2 = forward(tokens, _garbage(cfg, 4), table, zero)
        np.testing.assert_array_equal(clean, dirty)
        poisoned, p3 = forward(tokens, _garbage(cfg, 4, np.nan), table, zero)
        np.testing.assert_array_equal(clean, poisoned)
        for n in p1:
            np.testing.assert_array_equal(p1[n][:, 2], p2[n][:, 2])
            np.testing.assert_array_equal(p1[n][:, 2], p3[n][:, 2])
        # pos > 0 continues from the page: other logits than from zero
        cont, _ = forward(tokens, _garbage(cfg, 4), table, zero + 7)
        rezero, _ = forward(tokens, pr.init_paged_pool(cfg, 4, MAX_LEN),
                            table, zero + 7)
        assert not np.allclose(cont, rezero, **TOL)


def test_bucket_padding_leaves_the_state_alone(tiny):
    """(b) an 11-token prompt in a bucket of 16 leaves the page as the
    same 11 tokens in a window of their own leave it: rows past
    ``logit_pos`` add nothing and decay nothing."""
    cfg, params = tiny
    forward = forward_fn(cfg, params)
    p = PROMPTS[0]
    _, padded = admit(forward, pr.init_paged_pool(cfg, 3, MAX_LEN), 1, p)
    cfg1 = pr.PowerRetentionConfig.tiny(prefill_chunk=len(p))
    _, exact = forward_fn(cfg1, params)(
        jnp.asarray(p[None]), pr.init_paged_pool(cfg, 3, MAX_LEN),
        jnp.asarray([[1]], jnp.int32), jnp.zeros((1,), jnp.int32))
    for n in padded:
        np.testing.assert_allclose(padded[n][:, 1], exact[n][:, 1], **TOL)
        assert float(jnp.abs(exact[n][:, 1]).max()) > 0


@pytest.mark.parametrize("kernel", [False, True])
def test_dead_slots_touch_only_the_trash_page(tiny, kernel):
    """(c) a tick with two of three slots dead: their pages are bit for
    bit what they were, the live slot's page moved."""
    cfg, params = tiny
    with kernel_interpreted(kernel):
        forward = forward_fn(cfg, params)
        before = _garbage(cfg, 5)
        _, after = forward(
            jnp.asarray([[5], [6], [7]], jnp.int32), before,
            jnp.asarray([[1], [2], [3]], jnp.int32),
            jnp.asarray([4, 9, 2], jnp.int32),
            live=jnp.asarray([False, True, False]))
    for n in before:
        for page in (1, 3, 4):
            np.testing.assert_array_equal(after[n][:, page],
                                          before[n][:, page])
        assert not np.array_equal(after[n][:, 2], before[n][:, 2])


def engine(cfg, params, slots=4, **kw):
    return ServingEngine(cfg, params, slots=slots, max_len=MAX_LEN,
                         paged=True, page_size=MAX_LEN,
                         prompt_buckets=(WIDTH,), **kw)


def test_segment_program_holds_each_plane_once(tiny):
    """(d) the planes ride the while loop's carry and the layer scan's:
    the compiled ``('pseg', ...)`` program's temporaries stay under ONE
    plane (a copy of ``s`` a step, or a layer, would show as one; the
    published widths for a described v5e: ``test_chip_compile.py``), and
    the planes are donated through it."""
    from paddle_tpu.inference.program_space import WorkloadEnvelope

    cfg, params = tiny
    eng = engine(cfg, params, slots=8)
    warm = eng.aot_warmup(WorkloadEnvelope(
        max_prompt=WIDTH, max_new_tokens=8, seg_steps=(8,), resume=False))
    assert eng.pager.max_pages == 1 and eng.pager.num_pages == 9
    assert eng.pool_bytes["s"] == 2 * 9 * 2 * 768 * 32 * 4
    assert set(warm) == {"pseg"}
    assert warm["pseg"]["temp_bytes"] < eng.pool_bytes["s"]


# pages reused, pages forked ------------------------------------------------

def test_freed_page_readmitted_gives_a_fresh_engines_logits(tiny):
    """A page a finished request leaves as it was (no host-side clear) is
    handed to the next: its admission and ticks give bit for bit the
    logits they give over a pool that never held anything."""
    cfg, params = tiny
    _, _, used = paged_run(cfg, params, PROMPTS[:1], 5)
    assert float(jnp.abs(used["s"][:, 1]).max()) > 0
    again, full_a, _ = paged_run(cfg, params, PROMPTS[1:2], 4, pool=used)
    fresh, full_f, _ = paged_run(cfg, params, PROMPTS[1:2], 4)
    np.testing.assert_array_equal(full_a[0], full_f[0])
    for a, f in zip(again[0], fresh[0]):
        np.testing.assert_array_equal(a, f)


def test_one_slot_engine_reuses_its_page_across_requests(tiny):
    """The engine's own path: one slot, so every request after the first
    is admitted onto the page the one before it used."""
    cfg, params = tiny
    reqs = requests()[:3]
    eng = engine(cfg, params, slots=1)
    sched = OnlineScheduler(eng, max_queue=8, seg_steps=8)
    sched.serve(reqs)
    got = sched.results()
    assert eng.pager.num_pages == 2
    for j, a in enumerate(reqs):
        one = engine(cfg, params, slots=1)
        s1 = OnlineScheduler(one, max_queue=8, seg_steps=8)
        s1.serve([a])
        alone = s1.results()
        assert got[min(got) + j] == alone[min(alone)]


def test_ensure_writable_forks_a_state(tiny):
    """``fork_slot`` shares a state page, ``ensure_writable`` gives the
    fork a snapshot of its own (the pager's page-granular copy, over both
    planes); the two then decode apart, each like the reference's forward
    of its own sequence."""
    cfg, params = tiny
    m = sizes(cfg)
    forward = forward_fn(cfg, params)
    pager = PagedKVCache(cfg, slots=2, page_size=MAX_LEN, num_pages=4,
                         max_pages=1)
    pages, row = pager.reserve(MAX_LEN)
    pager.install(0, pages)
    pager.page_table = pager.page_table.at[0].set(jnp.asarray(row))
    p = PROMPTS[1]
    _, pager.pool = admit(forward, pager.pool, pages[0], p)
    pager.fork_slot(0, 1)
    assert pager.allocator.ref(pages[0]) == 2
    new = pager.ensure_writable(1, 0)
    assert new != pages[0] and pager.cow_breaks == 1
    for n, a in pager.pool.items():
        np.testing.assert_array_equal(a[:, new], a[:, pages[0]])
    tails = [np.asarray([7, 8, 9], np.int32), np.asarray([200, 3, 77],
                                                         np.int32)]
    pool = pager.pool
    for t in range(3):
        tok = np.array([[tails[0][t]], [tails[1][t]]], np.int32)
        logits, pool = forward(jnp.asarray(tok), pool, pager.page_table,
                               jnp.full((2,), len(p) + t, jnp.int32))
        for b in range(2):
            seq = np.concatenate([p, tails[b][:t + 1]])
            np.testing.assert_allclose(
                logits[b], ref_logits(params, seq, m)[-1], **TOL)


# the comparison has teeth ---------------------------------------------------

@pytest.mark.parametrize("fault", ["no_decay", "no_normaliser", "degree_1"])
def test_a_planted_fault_in_the_reference_shows(tiny, fault):
    """The program no longer agrees with a reference that drops the decay
    or the normaliser, or takes the first power: each moves the logits by
    a hundred times ``TOL`` or more."""
    cfg, params = tiny
    got, full, _ = paged_run(cfg, params, PROMPTS[:1], 3)
    want = ref_logits(params, full[0], sizes(cfg), (fault,))
    p = len(PROMPTS[0])
    for i, row in enumerate(got[0]):
        assert np.abs(row - want[p - 1 + i]).max() > 100 * TOL["atol"], \
            (fault, i)


# the engine -----------------------------------------------------------------

def requests():
    rng = np.random.RandomState(0)
    return [Arrival(0.0, rng.randint(0, 256, (n,)).astype(np.int32), g)
            for n, g in [(5, 6), (16, 4), (9, 8), (3, 5), (12, 7), (7, 3)]]


def serve(cfg, params):
    eng = engine(cfg, params)
    sched = OnlineScheduler(eng, max_queue=8, seg_steps=8)
    report = sched.serve(requests())
    return report, sched.results(), eng


def test_engine_serves_the_references_greedy_tokens(tiny):
    cfg, params = tiny
    assert family_of(cfg) is pr and family_of(llama.LlamaConfig.tiny()) \
        is llama
    report, results, eng = serve(cfg, params)
    m = sizes(cfg)
    rid0 = min(results)
    for rid, toks in results.items():
        a = requests()[rid - rid0]
        assert len(toks) == a.max_new_tokens
        seq = np.concatenate([a.prompt, toks[:-1]]).astype(np.int32)
        lg = ref_logits(params, seq, m)[len(a.prompt) - 1:]
        for t, row in zip(toks, lg):
            top2 = np.sort(row)[-2:]
            assert t == int(row.argmax()) or top2[1] - top2[0] < 1e-3
    # six requests through four state pages, all returned
    assert eng.pager.max_pages == 1 and not eng.pager.leak_report()
    # the counters rode the event log: a page a live slot a tick, and the
    # admissions' bucket rows beside the prompts' own
    gens = sum(a.max_new_tokens - 1 for a in requests())
    assert report.retention["state_pages"] == gens
    assert report.retention["admit_rows"] == WIDTH * len(requests())
    assert report.retention["admit_rows_used"] == sum(
        len(a.prompt) for a in requests())
    assert report.moe is None


class _Collector:
    def __init__(self):
        self.seen = []

    def _host_event(self, name, start_ns, end_ns, kind):
        self.seen.append(name)


def test_counters_and_tokens_identical_with_a_trace_live(tiny, tmp_path):
    from paddle_tpu.observability import metrics

    cfg, params = tiny
    before = metrics.counter("serving.retention.state_pages").value
    rep1, toks1, _ = serve(cfg, params)
    assert metrics.counter("serving.retention.state_pages").value - before \
        == rep1.retention["state_pages"]
    c = _Collector()
    _hooks.COLLECTORS.append(c)
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep2, toks2, _ = serve(cfg, params)
    finally:
        jax.profiler.stop_trace()
        _hooks.COLLECTORS.remove(c)
    assert toks1 == toks2 and rep1.retention == rep2.retention
    assert "serving.segment.telemetry" in c.seen


@pytest.mark.parametrize("rows", [1, WIDTH])
def test_scopes_are_in_the_program(tiny, rows):
    cfg, params = tiny
    pool = pr.init_paged_pool(cfg, 4, MAX_LEN)
    text = jax.jit(lambda p, t, pool, pt, pos: pr.forward_with_pages(
        p, t, cfg, pool, pt, pos)).lower(
            params, jnp.zeros((2, rows), jnp.int32), pool,
            jnp.ones((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32)
    ).as_text(debug_info=True)
    for scope in ("embed", "retention_qkv", "gate", "retention", "post",
                  "ffn"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope


def test_page_bytes_come_from_the_model(tiny):
    from paddle_tpu.analysis.memory import page_bytes_for

    cfg, params = tiny
    D = cfg.state_width
    assert D == 768
    pool = pr.init_paged_pool(cfg, 5, MAX_LEN)
    assert pool["s"].shape == (2, 5, 2, D, 32) and pool["s"].dtype == \
        jnp.float32 and pool["z"].shape == (2, 5, 2, D)
    # a page is a sequence's state in every layer, whatever page_size
    assert page_bytes_for(cfg, MAX_LEN) == page_bytes_for(cfg, 16) == \
        2 * 2 * D * 33 * 4
    big = pr.PowerRetentionConfig(num_layers=8)
    assert big.state_width == 9216
    assert page_bytes_for(big, 2048) == 8 * 8 * 9216 * 129 * 4
    with pytest.raises(ValueError, match="no quantized form"):
        pr.init_paged_pool(cfg, 5, MAX_LEN, quant="int8")
    with pytest.raises(ValueError, match="whole groups"):
        pr.PowerRetentionConfig.tiny(num_heads=3)


# the refusals ---------------------------------------------------------------

@pytest.mark.parametrize("family,kw", [
    ("chunked prefill", dict(chunked_prefill=True)),
    ("speculative", dict(speculative=2)),
    ("speculative", dict(sampling={"temperature": 0.7})),
    ("quality digest", dict(quality_digest=True)),
    ("quantized pool", dict(quant="int8")),
    ("sequence-parallel prefill", dict(seq_parallel=2, long_buckets=(32,))),
    ("mesh", dict(mesh=object())),
])
def test_unsupported_engine_families_refuse_by_name(tiny, family, kw):
    cfg, params = tiny
    with pytest.raises(ValueError, match=f"PowerRetentionConfig is not "
                                         f"served by the '{family}'"):
        engine(cfg, params, slots=2, **kw)


def test_prefix_cache_tiers_and_disagg_refuse_by_name(tiny):
    from paddle_tpu.inference.disagg import DisaggRouter
    from paddle_tpu.inference.kv_tiers import HostTier
    from paddle_tpu.inference.prefix_cache import PagedPrefixCache

    cfg, params = tiny
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match="'prefix cache'"):
        PagedPrefixCache(eng.pager)
    with pytest.raises(ValueError, match="'host tier'"):
        HostTier(eng.pager)
    with pytest.raises(ValueError, match="'disaggregated serving'"):
        DisaggRouter([eng], [engine(cfg, params)])
