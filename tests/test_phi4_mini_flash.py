"""The ``phi4_mini_flash`` block family (microsoft Phi-4-mini-flash-reasoning,
SambaY) on the paged engine, at toy widths on the CPU: 64 wide, eight
layers (Mamba, window, Mamba, window, the memory Mamba, the full layer, a
gated memory unit, a cross-attention layer), 8 query heads of 16 on 4 key
heads, values of 32 on 2, a window of 24 positions, 128 scan channels of 8
states; seeded weights.

The reference is ``benchmark/reference/phi4_mini_flash_f32.py`` (plain f32,
no cache, no kernels, no batching, a sequential scan, every token through
every layer: no skip; it imports nothing of the program).  Tolerance, f32:
1e-4 of the logits' standard deviation - program and reference do the same
f32 arithmetic and differ in reduction order only.  Contexts run to four
times the window and the prefill chunk is narrower (16) and wider (32, 64)
than it, so chunk boundaries fall mid-prompt and across the window, and
chunks, decode steps and chains all cross it.
"""

import importlib

import numpy as np
import pytest

VOCAB, WINDOW = 257, 24


def _pa():
    return importlib.import_module("pathway_tpu.kvcache.paged_attention")


def _cfg(dtype="float32", **over):
    import jax.numpy as jnp

    from pathway_tpu.models.phi4_mini_flash import Phi4MiniFlashConfig

    kw = dict(vocab_size=VOCAB, d_model=64, n_layers=8, n_heads=8,
              n_kv_heads=4, head_dim=16, d_ff=96, sliding_window=WINDOW,
              d_state=8, d_conv=4, expand=2, dt_rank=8, max_len=256,
              dtype=getattr(jnp, dtype))
    kw.update(over)
    return Phi4MiniFlashConfig(**kw)


def _shape(cfg):
    from benchmark.systems.serve_lfm2 import decoder_shape

    return decoder_shape(cfg, 0)


def _init(cfg, seed=0):
    import jax

    from pathway_tpu.models.phi4_mini_flash import init_phi4_mini_flash_params

    return init_phi4_mini_flash_params(cfg, jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return _init(cfg)


def _engine(cfg, params, name, **kw):
    from pathway_tpu.kvcache.engine import PagedDecodeEngine

    geom = dict(num_blocks=64, block_size=8, max_batch_size=4,
                chain_steps=4, prefill_chunk=16, seq_buckets=(64, 256),
                attn="reference")
    geom.update(kw)
    return PagedDecodeEngine(cfg, params, name=name, **geom)


def _prompts(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(4, VOCAB, n).tolist() for n in lengths]


REQS = [(70, 9), (7, 12), (101, 5), (3, 6), (55, 8), (33, 7)]


def _requests(seed=0):
    return [(p, n) for p, (_l, n) in zip(
        _prompts([l for l, _n in REQS], seed), REQS)]


def _reference(params, cfg, prompt, tokens):
    ref = importlib.import_module("benchmark.reference.phi4_mini_flash_f32")
    cols = np.arange(len(prompt) - 1, len(prompt) + len(tokens) - 1)
    return np.asarray(ref.logits_at(params, _shape(cfg), prompt + tokens,
                                    cols))


def _logits_through_engine(cfg, params, prompt, n_new, name, monkeypatch,
                           **kw):
    """One request alone through the engine's own programs (chunked prefill
    over the mixed step, chained decode, the single step at the tail),
    every program's logits caught where it turns them into ids.  Row 0 is
    the request."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import blocks

    caught = []

    def spy(logits):
        jax.debug.callback(lambda x: caught.append(np.asarray(x[0])), logits,
                           ordered=True)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(blocks, "greedy_ids", spy)
    eng = _engine(cfg, params, name, **kw)
    tokens = eng.generate(prompt, n_new)
    jax.effects_barrier()
    snap = eng.pool.stats.snapshot()
    assert snap["mixed_steps"] >= 2
    assert snap["chain_steps_sum"] > snap["chain_count"]  # really chained
    n_mixed = int(snap["mixed_steps"])
    rows = [caught[n_mixed - 1]] + caught[n_mixed:]
    assert len(rows) >= n_new
    return tokens, np.stack(rows[:n_new]), eng


def _rel_err(got, want):
    return np.abs(got - want).max(axis=-1) / want.std(axis=-1)


# -- the configuration --------------------------------------------------------


def test_published_layers_and_size():
    """The published stack: 9 Mamba layers (the memory one 16), 8 window
    layers, the full layer 17, 7 memory units and 7 cross-attention layers;
    3.85 billion parameters, 7.7 GB in bf16."""
    from pathway_tpu.models.phi4_mini_flash import (CROSS, GMU,
                                                    Phi4MiniFlashConfig)

    c = Phi4MiniFlashConfig()
    kinds = c.layer_types
    assert len(c.mamba_layers) == 9 and c.memory_layer == 16
    assert c.window_layers == tuple(range(1, 16, 2))
    assert c.full_layers == (17,) and c.n_self == 18
    assert kinds[18:] == (GMU, CROSS) * 7
    assert c.d_inner == 5120 and c.v_heads == 10
    assert 3.84e9 < c.param_count() < 3.86e9


def test_toy_parameters_are_counted(cfg, params):
    import jax

    n = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    assert n == cfg.param_count()


def test_published_scan_initialisation():
    """``A_log = log(1 .. d_state)`` a channel; the step ``softplus(dt_bias)``
    log-uniform in [1e-3, 1e-1]."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.phi4_mini_flash import mamba_init

    cfg = _cfg(d_model=512)
    p = mamba_init(jax.random.PRNGKey(1), cfg.d_inner, cfg.d_state)
    assert p["A_log"].shape == (cfg.d_state, cfg.d_inner)
    np.testing.assert_allclose(np.exp(np.asarray(p["A_log"][:, 0])),
                               np.arange(1, cfg.d_state + 1), rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    assert np.log(dt).std() > 0.9  # spread over the two decades
    assert bool(jnp.all(p["D"] == 1.0))


def test_the_benchmark_draws_the_scan_as_the_program_does():
    """The benchmark writes the published scan initialisation itself; on a
    seed it draws what the program's :func:`mamba_init` draws."""
    import jax

    from benchmark.weights_phi4_mini_flash import scan_init
    from pathway_tpu.models.phi4_mini_flash import mamba_init

    key = jax.random.PRNGKey(2147483647)
    ours, theirs = mamba_init(key, 320, 16), scan_init(key, 320, 16)
    assert set(ours) == set(theirs)
    for name in ours:
        np.testing.assert_array_equal(np.asarray(ours[name]),
                                      np.asarray(theirs[name]))


# -- the scan kernels ---------------------------------------------------------


def _scan_case(seed, T=40, E=256, N=16):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (T, E))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, E)) - 3)
    b, c = jax.random.normal(ks[2], (T, N)), jax.random.normal(ks[3], (T, N))
    a = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                          (N, E))
    state = np.asarray(jax.random.normal(ks[4], (2, 5, 1, N, E)))
    return u, dt, b, c, a, state


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["reference", "interpreted"])
def test_scan_matches_the_recurrence(kernels):
    """A packed step of a fresh row of 20 tokens (two items of 16), a decode
    row and a carried row of 19 against their slots, and an idle row: the
    chunk and step kernels' outputs and states are the token recurrence's;
    the other layer's arena is untouched."""
    import jax.numpy as jnp

    from pathway_tpu.ops import ssm

    u, dt, b, c, a, state = _scan_case(0)
    row_first = jnp.array([0, 20, 21, 0])
    row_nvalid = jnp.array([20, 1, 19, 1])
    fresh = jnp.array([True, False, False, True])
    row_slot = jnp.array([1, 2, 3, 0])
    live = jnp.array([True, True, True, False])
    items = ssm.chunk_items(row_first, fresh, row_nvalid, row_slot, live, 40,
                            16)
    y, st = ssm.mamba_mixed(u, dt, b, c, a, jnp.array(state), 1, items,
                            row_first, fresh, row_nvalid, row_slot, live,
                            use_pallas=kernels, interpret=True)
    st = np.asarray(st)
    for lo, hi, slot, s0 in ((0, 20, 1, np.zeros_like(state[1, 1, 0])),
                             (20, 21, 2, state[1, 2, 0]),
                             (21, 40, 3, state[1, 3, 0])):
        want, s = ssm.mamba_recurrence(u[lo:hi], dt[lo:hi], b[lo:hi],
                                       c[lo:hi], a, jnp.asarray(s0))
        np.testing.assert_allclose(np.asarray(y[lo:hi]), want, atol=1e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(st[1, slot, 0], s, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(st[0], state[0])


def test_step_kernel_matches_the_recurrence():
    import jax.numpy as jnp

    from pathway_tpu.ops import ssm

    u, dt, b, c, a, state = _scan_case(1, T=4)
    slots, fresh = jnp.array([4, 1, 0, 2]), jnp.array([False, True, False,
                                                       False])
    got = {}
    for kernels in (False, True):
        got[kernels] = ssm.mamba_decode(u, dt, b, c, a, jnp.array(state), 0,
                                        slots, fresh, use_pallas=kernels,
                                        interpret=True)
    for r in (0, 1, 3):
        s0 = np.zeros_like(state[0, 1, 0]) if r == 1 \
            else state[0, int(slots[r]), 0]
        want, s = ssm.mamba_recurrence(u[r:r + 1], dt[r:r + 1], b[r:r + 1],
                                       c[r:r + 1], a, jnp.asarray(s0))
        for y, st in got.values():
            np.testing.assert_allclose(np.asarray(y[r]), want[0], atol=1e-5,
                                       rtol=1e-5)
            np.testing.assert_allclose(np.asarray(st)[0, int(slots[r]), 0],
                                       s, atol=1e-5, rtol=1e-5)


def test_padding_leaves_the_state_alone():
    """A padded token (dt = 0, u = 0) is exp(0) = 1 and no update."""
    import jax.numpy as jnp

    from pathway_tpu.ops import ssm

    u, dt, b, c, a, state = _scan_case(2, T=8)
    s0 = jnp.asarray(state[0, 1, 0])
    _y, s = ssm.mamba_recurrence(u * 0, dt * 0, b, c, a, s0)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s0))


# -- the differential kernels -------------------------------------------------


@pytest.mark.parametrize("hd", [16, 64], ids=["one_group", "groups_of_two"])
def test_differential_kernels_match_the_gather_reference(hd):
    """Both maps of every query head over values shared by the two key
    halves (``v_heads``): the ragged kernel in pieces and the fused append,
    interpreted, against the gather reference, and that against the maps by
    hand (query head h on key head h // 2, value head (h // 2) % 2)."""
    import jax.numpy as jnp

    pa = _pa()
    rng = np.random.default_rng(0)
    H, KV, VH, BS, B, NB, C = 8, 4, 2, 16, 3, 4, 8
    kp = jnp.asarray(rng.normal(size=(2, 12, BS, KV * hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(2, 12, BS, VH * 2 * hd)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 8, 9, 0]],
                         jnp.int32)
    start = jnp.asarray([40, 3, 20], jnp.int32)
    nv = jnp.asarray([8, 1, 5], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, C, H, hd)), jnp.float32)
    kw = dict(start_pos=start, n_valid=nv, layer=1, v_heads=VH)
    ref = np.asarray(pa.paged_attention(q, kp, vp, tables, use_pallas=False,
                                        **kw))
    ker = np.asarray(pa.paged_attention(q, kp, vp, tables, use_pallas=True,
                                        interpret=True, **kw))
    k = np.asarray(kp[1][tables]).reshape(B, NB * BS, KV, hd)
    v = np.asarray(vp[1][tables]).reshape(B, NB * BS, VH, 2 * hd)
    for bi in range(B):
        for ci in range(int(nv[bi])):
            ctx = int(start[bi]) + ci + 1
            for h in range(H):
                s = k[bi, :ctx, h // 2] @ np.asarray(q[bi, ci, h]) \
                    / np.sqrt(hd)
                p = np.exp(s - s.max())
                want = (p / p.sum()) @ v[bi, :ctx, (h // 2) % VH]
                np.testing.assert_allclose(ref[bi, ci, h], want, atol=1e-5)
                np.testing.assert_allclose(ker[bi, ci, h], want, atol=1e-5)
    ctx = jnp.asarray([45, 4, 25], jnp.int32)
    sb, so = tables[jnp.arange(B), (ctx - 1) // BS], (ctx - 1) % BS
    kn = jnp.asarray(rng.normal(size=(B, KV, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, VH, 2 * hd)), jnp.float32)
    outs = [pa.paged_append_attend(q[:, :1], kn, vn, jnp.array(kp),
                                   jnp.array(vp), tables, ctx, sb, so,
                                   layer=1, use_pallas=p, interpret=True,
                                   v_heads=VH) for p in (False, True)]
    for x, y in zip(outs[0], outs[1]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)


# -- logits against the reference --------------------------------------------


@pytest.mark.parametrize("chunk", [16, 32, 64],
                         ids=["narrower", "wider", "wider_still"])
def test_f32_logits_match_the_reference(cfg, params, chunk, monkeypatch):
    """Prefill in chunks narrower and wider than the window of 24 (chunk
    boundaries mid-prompt, across the window), decode steps and a chain:
    the engine's logits (the cross-decoder on the emitting tokens alone) are
    the reference's full forward's (every token through every layer) within
    1e-4 of their deviation."""
    prompt = _prompts([70], seed=5)[0]
    tokens, got, eng = _logits_through_engine(
        cfg, params, prompt, 26, f"t_phi4_logits_{chunk}", monkeypatch,
        prefill_chunk=chunk)
    want = _reference(params, cfg, prompt, tokens)
    assert _rel_err(got, want).max() < 1e-4
    assert tokens == want.argmax(-1).tolist()
    eng.pool.check_invariants()
    assert eng.pool.cache_kind == "window_state"
    assert eng.pool.window_blocks_in_use == 0 and eng.pool.slots_in_use == 0
    assert eng.pool.stats.kv_window_blocks_freed > 0


def test_interpreted_kernels_match_the_reference(monkeypatch):
    """The scan, differential and writer kernels in interpret mode under the
    family, at key and value heads of whole lane tiles: the reference's
    logits within the same tolerance."""
    cfg = _cfg(head_dim=64, n_layers=4, d_model=64)
    params = _init(cfg, 2)
    prompt = _prompts([45], seed=6)[0]
    tokens, got, eng = _logits_through_engine(
        cfg, params, prompt, 7, "t_phi4_logits_pallas", monkeypatch,
        attn="pallas")
    want = _reference(params, cfg, prompt, tokens)
    assert _rel_err(got, want).max() < 1e-4
    assert eng.pool.k.shape[-1] == 256 and eng.pool.v.shape[-1] == 256
    assert eng.pool.state.shape[-2:] == (cfg.d_state, cfg.d_inner)


def test_mixed_rows_at_different_phases(cfg, params):
    """Six requests of 3 to 101 prompt tokens on four rows: chunks, decode
    rows and idle rows share steps, rows join as others finish; each emits
    what it emits alone, the argmax of the reference's logits."""
    reqs = _requests()
    eng = _engine(cfg, params, "t_phi4_batch")
    out = eng.generate_batch(reqs)
    eng.pool.check_invariants()
    assert eng.pool.sequences() == [] and eng.pool.slots_in_use == 0
    for (prompt, n), got in zip(reqs, out):
        alone = _engine(cfg, params, "t_phi4_alone").generate(prompt, n)
        assert got == alone
    prompt, n = reqs[2]
    want = _reference(params, cfg, prompt, out[2])
    assert out[2] == want.argmax(-1).tolist()


def _layer_matmul_rows(jaxpr, params) -> dict:
    """``{layer: the leading sizes of the operands its matrices (its leaves
    of two axes or more) multiply}`` in a jaxpr traced with ``params`` as
    its first arguments, through the calls it nests."""
    import jax
    from jax.extend.core import Var

    paths = jax.tree_util.tree_flatten_with_path(params)[0]
    owner = {}
    for var, (path, leaf) in zip(jaxpr.jaxpr.invars, paths):
        if path[0].key == "layers" and leaf.ndim >= 2:  # as the rule bills
            owner[var] = path[1].idx
    rows: dict = {}

    def walk(jx, owner):
        for e in jx.eqns:
            if e.primitive.name == "dot_general":
                a, b = e.invars
                for w, x in ((b, a), (a, b)):
                    if w in owner:
                        rows.setdefault(owner[w], set()).add(
                            x.aval.shape[0])
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub, {i: owner[o] for o, i in zip(e.invars, sub.invars)
                           if isinstance(o, Var) and o in owner})

    walk(jaxpr.jaxpr, owner)
    return rows


def test_the_skip_equals_a_forward_without_it():
    """The mixed program runs the cross-decoder on the B rows' emitting
    tokens: its memory units multiply (B, D) rows by their (D, E) gate,
    never the stream's (T, D), and the layers whose matrices take the B
    rows, and no token of the stream, are exactly the configuration's
    ``rows_only_layers``, which the chunk rule bills on the rows; and its
    logits at the token a row emits, wherever in the row it stands, are
    those of the reference, which runs every layer over every token."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import phi4_mini_flash as m

    cfg = _cfg(expand=3)  # E = 192: no other matrix is (64, 192)
    params = _init(cfg, 4)
    B, C, NB = 4, 16, 8
    T = B + C
    prompt = _prompts([C], seed=9)[0]
    cache = [jnp.zeros((1, 16, 8, 64)), jnp.zeros((1, 16, 8, 64)),
             jnp.zeros((2, 32, 8, 64)), jnp.zeros((2, 32, 8, 64)),
             jnp.zeros((3, 5, 3, cfg.d_inner)),
             jnp.zeros((3, 5, 1, cfg.d_state, cfg.d_inner))]

    def host(emitting):
        """One row of the prompt; ``emitting`` its token that emits."""
        tokens = np.zeros(T, np.int32)
        tokens[:C] = prompt
        positions = np.where(np.arange(T) < C, np.arange(T), 0)
        tables = np.zeros((B, NB), np.int32)
        tables[0, :2] = [1, 2]
        win = tables.copy()
        win[0, :2] = [3, 4]
        idx = np.zeros((B, C), np.int32)
        idx[0] = np.arange(C)
        tok_col = np.where(np.arange(T) < C, np.arange(T), 0)
        sb = np.where(np.arange(T) < C, 1 + np.arange(T) // 8, 0)
        so = np.where(np.arange(T) < C, np.arange(T) % 8, 0)
        return [jnp.asarray(a, jnp.int32) for a in (
            tokens, positions, tables, np.zeros(B), [C, 1, 1, 1], idx,
            np.zeros(T), tok_col, sb, so, [emitting, 0, 0, 0], win,
            [1, 0, 0, 0])]

    jaxpr = jax.make_jaxpr(lambda *a: m.window_state_mixed_step(
        params, cfg, *a))(*cache, *host(C - 1))
    gate = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"
            and e.invars[1].aval.shape == (cfg.d_model, cfg.d_inner)]
    shapes = {tuple(e.invars[0].aval.shape) for e in gate}
    assert shapes == {(B, cfg.d_model)}
    assert len(gate) == cfg.layer_types.count(m.GMU)
    by_layer = _layer_matmul_rows(jax.make_jaxpr(
        lambda p, *a: m.window_state_mixed_step(p, cfg, *a))(
            params, *cache, *host(C - 1)), params)
    assert sorted(by_layer) == list(range(cfg.n_layers))
    assert cfg.rows_only_layers == (6, 7)
    assert {li for li, seen in by_layer.items() if seen == {B}} \
        == set(cfg.rows_only_layers)
    assert {li for li, seen in by_layer.items() if seen == {T}} \
        == set(range(cfg.n_layers)) - set(cfg.rows_only_layers)
    ref = importlib.import_module("benchmark.reference.phi4_mini_flash_f32")
    want = np.asarray(ref.logits_at(params, _shape(cfg), prompt,
                                    np.arange(C)))
    got = {}
    for at in (C - 1, 5, 0):
        logits, *_cache, counts = m.window_state_mixed_step(
            params, cfg, *[jnp.array(a) for a in cache], *host(at))
        # the rows each decoder ran, as the program counts them
        assert np.asarray(counts[-2:]).tolist() == [B, T]
        got[at] = np.asarray(logits[:1])
        assert _rel_err(got[at], want[at:at + 1]).max() < 1e-4
    assert _rel_err(got[0], want[C - 1:]).max() > 1e-2


def test_a_fresh_row_never_reads_its_slots_last_owner(cfg, params):
    """One row: a long prompt, then short ones on the same slot, and a slot
    arena filled with NaN before the first: each emits what a fresh engine
    emits (the state starts from zero inside the program, the carried conv
    inputs are read only where their positions exist)."""
    import jax.numpy as jnp

    long, short = _prompts([90, 5], seed=11)
    fresh = _engine(cfg, params, "t_phi4_fresh", max_batch_size=1)
    want = fresh.generate(short, 6)
    eng = _engine(cfg, params, "t_phi4_reused", max_batch_size=1)
    eng.pool.conv = jnp.full_like(eng.pool.conv, jnp.nan)
    eng.pool.state = jnp.full_like(eng.pool.state, jnp.nan)
    eng.generate(long, 4)
    assert eng.generate(short, 6) == want
    assert eng.pool.stats.kda_state_resets >= 2


@pytest.mark.parametrize("fault", ["second_map_dropped", "conv_not_carried",
                                   "memory_from_two_layers_up",
                                   "bf16_scan_state"])
def test_a_fault_fails_the_f32_tolerance(cfg, params, fault, monkeypatch):
    """The planted faults of the cell's ``correct``, at toy widths: each
    moves the engine's logits far past the f32 tolerance."""
    import jax.numpy as jnp

    from pathway_tpu.models import phi4_mini_flash as m
    from pathway_tpu.ops import ssm

    if fault == "second_map_dropped":
        diff = m._differential
        monkeypatch.setattr(m, "_differential", lambda a, lay, *r: diff(
            a.at[:, a.shape[1] // 2:].set(0), lay, *r))
    elif fault == "conv_not_carried":
        inputs = m._mamba_inputs
        monkeypatch.setattr(m, "_mamba_inputs", lambda lay, cfg, h, conv_l,
                            *r: inputs(lay, cfg, h, conv_l * 0, *r))
    elif fault == "memory_from_two_layers_up":
        monkeypatch.setattr(m.Phi4MiniFlashConfig, "memory_layer",
                            property(lambda c: c.n_layers // 2 - 2))
    else:
        mixed, decode = ssm.mamba_mixed, ssm.mamba_decode

        def rounded(fn):
            def call(*a, **kw):
                y, st = fn(*a, **kw)
                return y, st.astype(jnp.bfloat16).astype(st.dtype)
            return call

        monkeypatch.setattr(ssm, "mamba_mixed", rounded(mixed))
        monkeypatch.setattr(ssm, "mamba_decode", rounded(decode))
    prompt = _prompts([70], seed=5)[0]
    tokens, got, _e = _logits_through_engine(
        cfg, params, prompt, 26, f"t_phi4_{fault}", monkeypatch)
    want = _reference(params, cfg, prompt, tokens)
    assert _rel_err(got, want).max() > 1e-3


# -- the family ----------------------------------------------------------------


@pytest.mark.parametrize("asked,named", [
    ({"tp": 2}, "tensor parallelism"),
    ({"quantize": "int8"}, "quantized plan of its weights"),
    ({"speculative": True}, "speculative drafting"),
    ({"session_store": object()}, "host tiering"),
])
def test_the_family_refuses_by_name(cfg, params, asked, named):
    with pytest.raises(ValueError, match="phi4_mini_flash") as e:
        _engine(cfg, params, "t_phi4_refused", **asked)
    assert named in str(e.value)


def test_rounds_note_the_cross_decoder_rows(cfg, params):
    """The mixed step program counts on the device the rows it ran the
    cross-decoder over (the batch's emitting rows) and the packed tokens it
    ran the self-decoder over (the step's width); the decode programs count
    neither."""
    eng = _engine(cfg, params, "t_phi4_rows")
    eng.generate_batch(_requests()[:3])
    eng.pool.after_sync()
    st = eng.pool.stats
    assert st.mixed_steps >= 2
    assert st.cross_decoder_rows == st.mixed_steps * eng.max_batch_size
    assert st.self_decoder_rows == st.mixed_steps * eng.mixed_tokens
    assert st.snapshot()["cross_decoder_rows"] == st.cross_decoder_rows


def test_a_vector_of_zero_experts_is_counted(cfg, params):
    """The programs' counter vector has no expert entries: the pool's
    stats take it as they take any other."""
    eng = _engine(cfg, params, "t_phi4_counts")
    eng.generate(_prompts([20], seed=3)[0], 3)
    eng.pool.after_sync()
    snap = eng.pool.stats.snapshot()
    assert snap["moe_routed_pairs"] == 0
    assert eng.pool.stats.moe_tokens_per_expert == []
