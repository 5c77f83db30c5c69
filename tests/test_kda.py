"""Kimi Delta Attention (``pathway_tpu.ops.kda``): the chunkwise form, in
plain ``jnp`` and in the interpreted kernels, against the token-by-token
recurrence, f32 on the CPU.  Both paths do the same f32 arithmetic in
another order: tolerance 2e-5 of the largest value (readings 2e-7 to 7e-7).
"""

import numpy as np
import pytest

H, DK = 2, 16


def _tokens(T, seed, decay=(1e-3, 0.3)):
    """q, k, kb, vb, g of T tokens: unit keys, scaled unit queries, a step
    size in (0, 1) a head, log decays between ``decay`` a token."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    q = rng.normal(size=(T, H, DK))
    k = rng.normal(size=(T, H, DK))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(DK)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(T, H, DK))
    beta = 1 / (1 + np.exp(-rng.normal(size=(T, H, 1))))
    lo, hi = np.log(decay[0]), np.log(decay[1])
    g = -np.exp(lo + rng.random(size=(T, H, DK)) * (hi - lo))
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q, k, k * beta, v * beta, g))


def _mixed(lens, starts, chunk, use_pallas, seed=0, n_rows=8, pad=5):
    """A packed step of rows of ``lens`` tokens (row r in slot r + 1, its
    sequence at ``starts[r]``) through ``kda_mixed``; returns the stream,
    the output, the arenas before and after, and the rows' places."""
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    T = sum(lens) + pad
    toks = _tokens(T, seed)
    rng = np.random.default_rng(seed + 1)
    state = jnp.asarray(rng.normal(size=(2, n_rows + 1, H, DK, DK)),
                        jnp.float32)
    first = np.zeros(n_rows, np.int32)
    nvalid = np.ones(n_rows, np.int32)
    start = np.zeros(n_rows, np.int32)
    slot = np.zeros(n_rows, np.int32)
    live = np.zeros(n_rows, bool)
    t = 0
    for r, n in enumerate(lens):
        first[r], nvalid[r], start[r] = t, n, starts[r]
        slot[r], live[r] = r + 1, True
        t += n
    J = jnp.asarray
    items = kda.chunk_items(J(first), J(start == 0), J(nvalid), J(slot),
                            J(live), T, chunk)
    o, after = kda.kda_mixed(
        *toks, jnp.array(state), 1, items, J(first), J(start == 0),
        J(nvalid), J(slot), J(live), use_pallas=use_pallas)
    return toks, o, state, after, first, items


def _recurrence(toks, sl, s0):
    from pathway_tpu.ops import kda

    return kda.kda_recurrence(*(x[sl] for x in toks), s0)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "interpreted_kernels"])
@pytest.mark.parametrize("length", [1, 63, 64, 65, 256])
def test_chunked_form_equals_the_recurrence(length, use_pallas):
    """One row of ``length`` tokens carried on from a state (chunks of 64:
    one token takes the step kernel, 63 a padded item, 64 a whole one, 65
    an item and a token more, 256 four items whose state is carried)."""
    toks, o, before, after, first, items = _mixed(
        [length], [7], 64, use_pallas, seed=length)
    want_o, want_s = _recurrence(toks, slice(0, length), before[1, 1])
    np.testing.assert_allclose(o[:length], want_o, atol=2e-5)
    np.testing.assert_allclose(after[1, 1], want_s, atol=2e-5)
    assert int(items["n_live"]) == (0 if length == 1 else -(-length // 64))
    assert float(np.abs(o[length:]).max()) == 0.0   # padding tokens
    np.testing.assert_array_equal(after[0], before[0])  # another layer's


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "interpreted_kernels"])
def test_rows_of_unequal_length_share_a_step(use_pallas):
    """Decode rows, a first chunk (from zero whatever its slot held), a
    continued chunk and a one-token remainder in one packed stream: every
    row against the recurrence from its own state, the null slot's rows
    and the slots of rows that are not there untouched."""
    lens, starts = [1, 37, 1, 80, 1, 16], [9, 0, 0, 48, 5, 0]
    toks, o, before, after, first, _items = _mixed(lens, starts, 16,
                                                   use_pallas)
    for r, (n, s) in enumerate(zip(lens, starts)):
        s0 = np.zeros((H, DK, DK), np.float32) if s == 0 else before[1, r + 1]
        sl = slice(int(first[r]), int(first[r]) + n)
        want_o, want_s = _recurrence(toks, sl, s0)
        np.testing.assert_allclose(o[sl], want_o, atol=2e-5)
        np.testing.assert_allclose(after[1, r + 1], want_s, atol=2e-5)
    np.testing.assert_array_equal(after[1, 7:], before[1, 7:])


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "interpreted_kernels"])
def test_a_padded_chunk_leaves_the_state_as_the_valid_tokens_left_it(
        use_pallas):
    """A row of 21 tokens in items of 16: the second item's eleven padded
    tokens (another row's tokens lie there in the stream) change nothing."""
    toks, o, before, after, first, items = _mixed([21, 30], [3, 0], 16,
                                                  use_pallas)
    assert np.asarray(items["valid"]).sum() == 51
    _o, want_s = _recurrence(toks, slice(0, 21), before[1, 1])
    np.testing.assert_allclose(after[1, 1], want_s, atol=2e-5)


@pytest.mark.parametrize("n", [8, 16, 32, 64, 128])
def test_unit_lower_inverse_is_the_inverse(n):
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    rng = np.random.default_rng(n)
    a = np.tril(rng.normal(size=(n, n)) * 0.1, -1).astype(np.float32)
    got = np.asarray(kda._unit_lower_inverse(jnp.asarray(a)))
    np.testing.assert_allclose(got @ (np.eye(n) + a), np.eye(n), atol=5e-5)


def test_unit_lower_inverse_survives_identical_keys():
    """Identical keys at step size 1: ``a`` is all ones under the diagonal,
    whose powers over a whole chunk would pass 1e30; the inverse itself is
    the bidiagonal (1, -1)."""
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    n = 128
    a = np.tril(np.ones((n, n), np.float32), -1)
    got = np.asarray(kda._unit_lower_inverse(jnp.asarray(a)))
    want = np.eye(n) - np.eye(n, k=-1)
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "interpreted_kernels"])
def test_decode_rows_take_the_recurrence(use_pallas):
    """``kda_decode``: one token a row against its slot; a fresh row starts
    from zero, an idle row rides the null slot and changes no other."""
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    B = 4
    toks = _tokens(B, 3)
    rng = np.random.default_rng(4)
    state = jnp.asarray(rng.normal(size=(3, B + 1, H, DK, DK)), jnp.float32)
    slot = jnp.asarray([2, 0, 4, 1], jnp.int32)
    fresh = jnp.asarray([False, False, True, False])
    o, after = kda.kda_decode(*toks, jnp.array(state), 2, slot, fresh,
                              use_pallas=use_pallas)
    for b in (0, 2, 3):
        s0 = np.zeros((H, DK, DK), np.float32) if bool(fresh[b]) \
            else state[2, int(slot[b])]
        want_o, want_s = _recurrence(toks, slice(b, b + 1), s0)
        np.testing.assert_allclose(o[b], want_o[0], atol=2e-5)
        np.testing.assert_allclose(after[2, int(slot[b])], want_s, atol=2e-5)
    np.testing.assert_array_equal(after[2, 3], state[2, 3])
    np.testing.assert_array_equal(after[:2], state[:2])


def test_strong_decays_stay_finite():
    """Decays down to 0.3 a token over a chunk of 64: the exponents are
    taken against the chunk's middle, so no factor overflows f32."""
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    toks = _tokens(64, 9, decay=(0.5, 1.2))
    s0 = jnp.zeros((H, DK, DK), jnp.float32)
    math = __import__("jax").vmap(kda._chunk_math,
                                  in_axes=(1, 1, 1, 1, 1, 0),
                                  out_axes=(1, 0))
    o, s1 = math(*toks, s0)
    want_o, want_s = kda.kda_recurrence(*toks, s0)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(s1, want_s, atol=2e-5)


def test_work_items_cover_every_long_row_once():
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    nvalid = jnp.asarray([1, 40, 1, 16, 17, 1], jnp.int32)
    first = jnp.asarray([0, 1, 41, 42, 58, 0], jnp.int32)
    start = jnp.asarray([5, 0, 3, 16, 0, 0], jnp.int32)
    slot = jnp.asarray([1, 2, 3, 4, 5, 0], jnp.int32)
    live = jnp.asarray([1, 1, 1, 1, 1, 0], bool)
    it = kda.chunk_items(first, start == 0, nvalid, slot, live, 80, 16)
    assert it["token"].shape == (kda.n_items(80, 6, 16), 16) == (11, 16)
    assert int(it["n_live"]) == 3 + 1 + 2
    seen = np.asarray(it["token"])[np.asarray(it["valid"])]
    assert sorted(seen.tolist()) == list(range(1, 41)) + list(range(42, 75))
    flag = np.asarray(it["flag"])
    assert flag[:6].tolist() == [
        kda._LIVE | kda._FIRST | kda._FRESH, kda._LIVE, kda._LIVE,
        kda._LIVE | kda._FIRST, kda._LIVE | kda._FIRST | kda._FRESH,
        kda._LIVE]
    assert not (flag[6:] & kda._LIVE).any()
    # a dead item repeats the last live item's slot: no block moves
    assert np.asarray(it["slot"]).tolist() == [2, 2, 2, 4, 5, 5] + [5] * 5


# -- one decay a head (PR 38: the gated delta rule of models/qwen3_next.py) ----


def _head_tokens(T, seed, key_heads=None):
    """``_tokens`` with ONE log decay a head and token, g (T, H); with
    ``key_heads`` fewer key heads than value heads: q, k of key head
    ``h // (H // key_heads)`` repeated for value head ``h``."""
    import jax.numpy as jnp

    q, k, _kb, vb, g = _tokens(T, seed)
    rng = np.random.default_rng(seed + 100)
    beta = jnp.asarray(1 / (1 + np.exp(-rng.normal(size=(T, H, 1)))),
                       jnp.float32)
    if key_heads:
        rep = H // key_heads
        q = jnp.repeat(q[:, :key_heads], rep, axis=1)
        k = jnp.repeat(k[:, :key_heads], rep, axis=1)
    return q, k, k * beta, vb, g[..., 0]


def test_one_decay_a_head_is_the_channel_rule_with_equal_channels():
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    q, k, kb, vb, g = _head_tokens(40, seed=3)
    s0 = jnp.asarray(np.random.default_rng(4).normal(size=(H, DK, DK)),
                     jnp.float32)
    o1, s1 = kda.kda_recurrence(q, k, kb, vb, g, s0)
    wide = jnp.broadcast_to(g[..., None], g.shape + (DK,))
    o2, s2 = kda.kda_recurrence(q, k, kb, vb, wide, s0)
    np.testing.assert_array_equal(o1, o2)
    np.testing.assert_array_equal(s1, s2)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "interpreted_kernels"])
@pytest.mark.parametrize("key_heads", [None, 1], ids=["h_eq", "h_div_2"])
def test_one_decay_a_head_through_both_kernels(use_pallas, key_heads):
    """A packed step whose decay comes as (T, H): a carried chunk of 70
    tokens in items of 16 (the chunk kernel: a head's column of the item's
    (n, H) block, broadcast over the channels in VMEM), a fresh chunk, a
    decode row and a one-token remainder (the step kernel: one number a
    head), against the token recurrence; ``h_div_2``: both value heads read
    the ONE key head's q and k (value head h, key head h // 2)."""
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    lens, starts = [70, 1, 23, 1], [9, 40, 0, 5]
    T = sum(lens) + 3
    toks = _head_tokens(T, seed=11, key_heads=key_heads)
    assert toks[4].shape == (T, H)
    if key_heads:
        np.testing.assert_array_equal(toks[0][:, 0], toks[0][:, 1])
    rng = np.random.default_rng(12)
    state = jnp.asarray(rng.normal(size=(2, 6, H, DK, DK)), jnp.float32)
    n_rows = 5
    first = np.zeros(n_rows, np.int32)
    nvalid = np.ones(n_rows, np.int32)
    start = np.zeros(n_rows, np.int32)
    slot = np.zeros(n_rows, np.int32)
    live = np.zeros(n_rows, bool)
    t = 0
    for r, n in enumerate(lens):
        first[r], nvalid[r], start[r] = t, n, starts[r]
        slot[r], live[r] = r + 1, True
        t += n
    J = jnp.asarray
    items = kda.chunk_items(J(first), J(start == 0), J(nvalid), J(slot),
                            J(live), T, 16)
    o, after = kda.kda_mixed(
        *toks, jnp.array(state), 1, items, J(first), J(start == 0),
        J(nvalid), J(slot), J(live), use_pallas=use_pallas)
    for r, n in enumerate(lens):
        sl = slice(first[r], first[r] + n)
        s0 = jnp.zeros((H, DK, DK)) if starts[r] == 0 else state[1, r + 1]
        want_o, want_s = _recurrence(toks, sl, s0)
        np.testing.assert_allclose(o[sl], want_o, atol=2e-5)
        np.testing.assert_allclose(after[1, r + 1], want_s, atol=2e-5)
    np.testing.assert_array_equal(after[0], state[0])  # another layer's
    np.testing.assert_array_equal(after[1, 5], state[1, 5])  # no row's slot


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "interpreted_kernels"])
def test_decode_rows_with_one_decay_a_head(use_pallas):
    import jax.numpy as jnp

    from pathway_tpu.ops import kda

    B = 5
    q, k, kb, vb, g = _head_tokens(B, seed=21)
    rng = np.random.default_rng(22)
    state = jnp.asarray(rng.normal(size=(3, B + 1, H, DK, DK)), jnp.float32)
    slots = jnp.asarray([3, 1, 0, 5, 2], jnp.int32)
    fresh = jnp.asarray([0, 1, 0, 0, 0], jnp.int32)
    o, after = kda.kda_decode(q, k, kb, vb, g, jnp.array(state), 2, slots,
                              fresh, use_pallas=use_pallas)
    for b in (0, 1, 3, 4):
        s0 = jnp.zeros((H, DK, DK)) if fresh[b] else state[2, slots[b]]
        want_o, want_s = _recurrence((q, k, kb, vb, g), slice(b, b + 1), s0)
        np.testing.assert_allclose(o[b], want_o[0], atol=2e-5)
        np.testing.assert_allclose(after[2, slots[b]], want_s, atol=2e-5)
    np.testing.assert_array_equal(after[:2], state[:2])


# What the two jitted functions trace to with a decay a CHANNEL at PR 38's
# parent, source positions removed: sha256 of the whole jaxpr and of the
# kernel's (dead code eliminated), first 16 digits.  They change with JAX's
# printer; a change that is meant to touch the per-channel path prints the
# new ones in its failure.
_PARENT_KDA = {
    "chunk": ("b39f76a37b91cfde", "c15cff95efe7c620"),
    "step": ("e7f2c3aa2cc33918", "3089d8f9d795d0fb"),
}


@pytest.mark.parametrize("which", list(_PARENT_KDA))
def test_per_channel_kernels_trace_to_the_parents_jaxprs(which):
    """One decay a head is a branch on ``g``'s shape in Python: with a decay
    a channel ``_kda_chunk_fn`` and ``_kda_step_fn`` trace to the jaxprs
    they traced to before, so the per-channel family's programs lower and
    run as they did."""
    import hashlib
    import re

    import jax
    import jax.numpy as jnp
    from jax._src.interpreters import partial_eval as pe

    from pathway_tpu.ops import kda

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    bf, f32 = jnp.bfloat16, jnp.float32
    NW, n, Hh, d, B = 5, 128, 32, 128, 4
    arena = S((2, 5, Hh, d, d), f32)
    if which == "chunk":
        tok = S((NW, n, Hh * d), bf)
        fn, args = kda._kda_chunk_fn, (
            tok, tok, tok, tok, S((NW, n, Hh * d), f32), arena, S((1,)),
            S((NW,)), S((NW,)))
    else:
        col = S((B, d, Hh), f32)
        fn, args = kda._kda_step_fn, (
            col, col, col, col, S((B, Hh, d), bf), arena, S((1,)), S((B,)),
            S((B,)))
    closed = jax.make_jaxpr(fn)(*args)
    (call,) = [e for e in closed.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    kernel = call.params["jaxpr"]
    kernel, _ = pe.dce_jaxpr(kernel, [True] * len(kernel.outvars))

    def digest(x):
        text = re.sub(r" at [^ ]*:\d+", "", str(x))
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    assert (digest(closed), digest(kernel)) == _PARENT_KDA[which]
