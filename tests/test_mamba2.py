"""The Mamba-2 operators (``ray_tpu/ops/mamba2.py``), the decode kernel
that updates the lanes' states in place (``ops/pallas_mamba2.py``, in
interpret mode) and the dense grouped-query paged decode
(``ops/pallas_gqa_paged_attention.py``), on the CPU in float32 against
the recurrence written out in numpy.

Tolerances: all float32; the chunked form sums a block's positions as
matmuls where the recurrence adds them one by one: 1e-6 to 4e-6 seen on
outputs of size 1-10, so 5e-5.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import mamba2
from ray_tpu.ops.attention import gqa_paged_decode_attention, reference_decode_attention
from ray_tpu.ops.pallas_gqa_paged_attention import gqa_paged_decode_attention_kernel
from ray_tpu.ops.pallas_mamba2 import mamba2_decode_step

TOL = 5e-5
H, P, N, G, K = 8, 8, 16, 2, 4  # heads, head size, state size, groups, convolution width
CHUNK = 8  # positions a block of the chunked scan


def _sequence(T, seed=0, H=H, G=G):
    """x [T, H, P], dt [T, H] (after its softplus), A, D [H], B, C [T, G, N]."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.log1p(np.exp(rng.normal(size=(T, H)))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, H), jnp.float32)
    return f(T, H, P), dt, A, f(T, G, N), f(T, G, N), jnp.asarray(rng.normal(size=H), jnp.float32)


def _recurrence(x, dt, A, B, C, D, S=None):
    """The equations a position at a time, in numpy float64."""
    x, dt, A, B, C, D = (np.asarray(v, np.float64) for v in (x, dt, A, B, C, D))
    T, H = x.shape[:2]
    G = B.shape[1]
    S = np.zeros((H, P, N)) if S is None else np.asarray(S, np.float64)
    out = np.zeros((T, H, P))
    for t in range(T):
        for h in range(H):
            g = h // (H // G)
            S[h] = np.exp(dt[t, h] * A[h]) * S[h] + dt[t, h] * np.outer(x[t, h], B[t, g])
            out[t, h] = S[h] @ C[t, g] + D[h] * x[t, h]
    return out, S


def _in_pieces(seq, cuts, pad_to=None, chunk=CHUNK):
    """``ssd_chunk`` over the sequence cut at ``cuts``, the state carried
    from piece to piece; a piece is padded to ``pad_to`` positions (or to
    whole blocks) with rows that must change nothing."""
    x, dt, A, B, C, D = seq
    T = x.shape[0]
    S, outs = jnp.zeros((x.shape[1], P, N), jnp.float32), []
    for a, b in zip((0, *cuts), (*cuts, T)):
        n = b - a
        width = pad_to or (n if n < chunk else -(-n // chunk) * chunk)
        pad = lambda v: jnp.concatenate([v[a:b], 7.0 + jnp.zeros((width - n, *v.shape[1:]), v.dtype)])  # noqa: E731
        y, S = mamba2.ssd_chunk(pad(x), pad(dt), A, pad(B), pad(C), D, S, n, chunk)
        outs.append(y[:n])
    return jnp.concatenate(outs), S


@pytest.mark.parametrize("cuts", [
    (),            # one piece of three blocks and a padded fourth
    (8,),          # cut AT a block's boundary
    (5,),          # cut inside a block
    (1,),          # a single token first
    (8, 9, 20),    # a boundary, a single token, inside a block
    (16, 24),      # whole blocks only
], ids=str)
def test_ssd_chunk_over_any_split_is_the_recurrence(cuts):
    seq = _sequence(27, seed=len(cuts))
    want, want_S = _recurrence(*seq)
    got, S = _in_pieces(seq, cuts)
    assert np.abs(np.asarray(got) - want).max() < TOL
    assert np.abs(np.asarray(S) - want_S).max() < TOL


def test_ssd_chunk_pads_leave_the_state_alone():
    """A piece of 5 real positions in a bucket of 16 (two blocks, the
    second all pads, the pads' values wild): the state is the one after
    position 4, and going on from it is going on from there."""
    seq = _sequence(12, seed=3)
    want, want_S = _recurrence(*seq)
    got, S = _in_pieces(seq, (5,), pad_to=16)
    assert np.abs(np.asarray(got) - want).max() < TOL and np.abs(np.asarray(S) - want_S).max() < TOL


def test_ssd_chunk_at_blocks_of_256_with_one_group_carries_its_state_across_chunks():
    """Granite 4.0-H's form: ONE group for all the heads and scan blocks
    of 256, where a fast head's decay over a block underflows (``exp`` of
    a sum of 256 terms down to -16 each) and must do so without a
    quotient of two such.  Two chunks of 512 positions (two blocks each)
    and a third of 100 in a padded block, the state carried between.
    Steps log-uniform in the published [0.001, 0.1]: a block's log-decay
    then reaches -400, of which a float32 keeps 3e-5, and what is left
    between two positions is ``exp`` of a difference of two such: 1e-4
    seen on outputs of size 1-10, so 5e-4 (blocks of 8 never get there)."""
    x, _, A, B, C, D = _sequence(1124, seed=11, H=4, G=1)
    dt = jnp.asarray(np.exp(np.random.default_rng(12).uniform(np.log(1e-3), np.log(0.1), (1124, 4))), jnp.float32)
    # head 0 is the fastest there can be: its decay over a block, exp(-409.6), is 0 in float32
    seq = (x, dt.at[:, 0].set(0.1), A.at[0].set(-16.0), B, C, D)
    want, want_S = _recurrence(*seq)
    got, S = _in_pieces(seq, (512, 1024), chunk=256)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got) - want).max() < 10 * TOL
    assert np.abs(np.asarray(S) - want_S).max() < 10 * TOL


def test_ssm_step_token_by_token_is_ssd_chunk():
    """Decode's form from the state a chunk left: lanes are sequences at
    different positions, a fourth lane idle (its state stays)."""
    A, D = _sequence(1, seed=4)[2], _sequence(1, seed=4)[5]  # one A and D for the lanes of a layer
    seqs = [(s[0], s[1], A, s[3], s[4], D) for s in (_sequence(20, seed=k) for k in (5, 6, 7))]
    n_prompt = (9, 16, 3)
    wants = [_recurrence(*seq)[0] for seq in seqs]
    states = [_in_pieces(tuple(v[:n] if v.ndim > 1 else v for v in seq), ())[1] for seq, n in zip(seqs, n_prompt)]
    state = jnp.stack(states + [jnp.full((H, P, N), 3.0)])
    for step in range(4):
        at = [n + step for n in n_prompt]
        take = lambda i: jnp.stack([seqs[j][i][t] for j, t in enumerate(at)] + [seqs[0][i][0]])  # noqa: E731
        y, state = mamba2.ssm_step(take(0), take(1), A, take(3), take(4), D, state,
                                   jnp.asarray([True, True, True, False]))
        for lane, t in enumerate(at):
            assert np.abs(np.asarray(y[lane]) - wants[lane][t]).max() < TOL
    assert (np.asarray(state[3]) == 3.0).all()


@pytest.mark.parametrize("cuts", [(), (3,), (8,), (1, 2), (11, 12, 13)], ids=str)
def test_conv_tail_across_a_boundary_is_the_whole_convolution(cuts):
    """The tail a piece leaves is what the next piece needs: the pieces'
    outputs are the whole sequence's, pads after a piece's last real row
    (wild values) change neither; a piece of one token takes the decode
    form."""
    rng = np.random.default_rng(2)
    T, C = 17, 6
    x = jnp.asarray(rng.normal(size=(T, C)), jnp.float32)
    w, b = jnp.asarray(rng.normal(size=(C, K)), jnp.float32), jnp.asarray(rng.normal(size=C), jnp.float32)
    padded = np.concatenate([np.zeros((K - 1, C)), np.asarray(x)])
    acc = np.asarray(b) + sum(np.asarray(w)[:, j] * padded[j:j + T] for j in range(K))
    want = acc / (1 + np.exp(-acc))
    tail, outs = jnp.zeros(((K - 1) * C,), jnp.float32), []
    for a, e in zip((0, *cuts), (*cuts, T)):
        n = e - a
        if n == 1:
            y, tail = mamba2.conv_tail(x[a:e], tail, w, b)
        else:
            piece = jnp.concatenate([x[a:e], jnp.full((3, C), 9.0)])
            y, tail = mamba2.conv_tail(piece, tail, w, b, n)
        outs.append(y[:n])
    assert np.abs(np.asarray(jnp.concatenate(outs)) - want).max() < 1e-5
    assert np.array_equal(np.asarray(tail).reshape(K - 1, C), np.asarray(x[-(K - 1):]))


def _lanes(Bn, seed=0, H=H, G=G, P=P):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.log1p(np.exp(rng.normal(size=(Bn, H)))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, H), jnp.float32)
    state = jnp.asarray(rng.normal(size=(Bn, H, P, 128)), jnp.float32)
    return f(Bn, H, P), dt, A, f(Bn, G, 128), f(Bn, G, 128), jnp.asarray(rng.normal(size=H), jnp.float32), state


def _cancelling(args, seed, big=300.0):
    """The same lanes, made so that the new state's products with C are
    large and cancel: the state's columns and B's are equal in pairs (a
    random pairing of the N columns, so the new state's are too, bit for
    bit) and C is ``+big r`` on one of a pair and ``-big r`` on the
    other, plus the small C it had.  A term is hundreds of times the
    sum, and the sum's order shows."""
    B, C, state = np.array(args[3]), 0.01 * np.asarray(args[4]), np.array(args[6])
    rng = np.random.default_rng(seed)
    pair = rng.permutation(state.shape[-1])
    one, other = pair[0::2], pair[1::2]
    state[..., other], B[..., other] = state[..., one], B[..., one]
    r = big * rng.normal(size=C[..., one].shape).astype(np.float32)
    C[..., one] += r
    C[..., other] -= r
    return (*args[:3], jnp.asarray(B), jnp.asarray(C), args[5], jnp.asarray(state))


@pytest.mark.parametrize("active, heads, groups, rows, tol, cancelling", [
    ([True] * 5, H, G, P, 1e-5, False), ([True, False, True, True, False], H, G, P, 1e-5, False),
    ([False, False, True, False, False], H, G, P, 1e-5, False), ([False] * 5, H, G, P, 1e-5, False),
    # Granite 4.0-H: 128 heads that share ONE B and C; the largest of sixteen times the outputs lies higher
    ([True, True, False, True, True], 128, 1, P, 5e-5, False),
    ([False, False, False, True, False], 128, 1, P, 5e-5, False),   # one lane alone running at that shape
    # heads of the served 64 rows, two of them a stationary operand: 4 a group, and 2 of 3 groups
    ([True, False, True], 8, 2, 64, 5e-5, False), ([True, True, True], 6, 3, 64, 5e-5, False),
    # products with C that are large and cancel: the distance is held against the terms' size, see below
    ([True] * 5, H, G, P, 1e-5, True), ([True, True, False, True, True], 128, 1, P, 5e-5, True),
], ids=str)
def test_mamba2_decode_step_kernel_is_ssm_step(active, heads, groups, rows, tol, cancelling):
    """The Pallas kernel in interpret mode: the running lanes' outputs
    and states are ``ssm_step``'s, an idle lane's state is the array's
    own bits.  The outputs are held to the tolerance HEAD BY HEAD ([lane,
    head] maxima: a head's rows put where another's belong would pass a
    maximum over everything only by luck) and, where the products
    cancel, against float64: both float32 sums then lie within two
    units in the last place of the TERMS (not of the sum) from it, and
    the kernel's no further than ``ssm_step``'s own order of the sum
    does, twice over."""
    args = _lanes(len(active), seed=1, H=heads, G=groups, P=rows)
    if cancelling:
        args = _cancelling(args, seed=2)
    active = jnp.asarray(active)
    want_y, want_S = mamba2.ssm_step(*args, active)
    y, S = mamba2_decode_step(*args, active, interpret=True)
    on = np.asarray(active)
    assert y.shape == want_y.shape and S.shape == want_S.shape
    assert np.abs(np.asarray(S) - np.asarray(want_S))[on].max(initial=0.0) < tol
    assert np.array_equal(np.asarray(S)[~on], np.asarray(args[-1])[~on])
    by_head = np.abs(np.asarray(y) - np.asarray(want_y)).max(axis=-1)[on]        # [running lane, head]
    if not cancelling:
        assert (by_head < tol).all(), np.argwhere(by_head >= tol)[:5]
        return
    x, dt, A, B, C, D, state = (np.asarray(a, np.float64) for a in args)
    R = heads // groups
    new = (np.exp(dt * A)[..., None, None] * state
           + (dt[..., None] * x)[..., None] * np.repeat(B, R, axis=1)[:, :, None, :])
    terms = new * np.repeat(C, R, axis=1)[:, :, None, :]
    true = terms.sum(-1) + D[:, None] * x
    size = np.abs(terms).sum(-1).max(axis=-1)[on]                                # [running lane, head]: sum of |terms|
    assert (size > 100 * np.abs(true).max(axis=-1)[on]).all()                    # they do cancel
    mine = np.abs(np.asarray(y, np.float64) - true).max(axis=-1)[on]
    theirs = np.abs(np.asarray(want_y, np.float64) - true).max(axis=-1)[on]
    eps = np.finfo(np.float32).eps
    assert (mine < 2 * eps * size).all(), (mine / (eps * size)).max()
    assert mine.max() < 2 * theirs.max() + tol


def test_mamba2_decode_step_writes_the_buffer_it_read():
    """The kernel's state operand is its state result (counting the two
    prefetched scalars it is operand 6, result 1): no second array of
    states."""
    args = (*_lanes(3), jnp.ones(3, bool))
    jaxpr = jax.make_jaxpr(lambda *a: mamba2_decode_step(*a, interpret=True))(*args)

    def calls(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (call,) = calls(jaxpr.jaxpr)
    assert tuple(call.params["input_output_aliases"]) == ((6, 1),)
    assert call.invars[6].aval.shape == call.outvars[1].aval.shape == (3, H, P, 128)
    assert call.params["name"] == "mamba2_decode_step"  # its name in the device trace


# ----------------------------------------------------------------------
# the dense grouped-query paged decode
# ----------------------------------------------------------------------
def _paged(B, Gk, R, Dh, bs, pages, dtype, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), dtype)  # noqa: E731
    blocks = B * pages + 1
    tables = (rng.permutation(blocks - 1)[:B * pages] + 1).reshape(B, pages).astype(np.int32)
    lengths = rng.integers(1, pages * bs, B).astype(np.int32)
    lengths[1], lengths[2] = 0, pages * bs - 1  # a lane that holds nothing, a lane that is nearly full
    return (f(B, Gk, R, Dh), f(B, Gk, Dh), f(B, Gk, Dh), f(2, blocks * bs, Gk * Dh), f(2, blocks * bs, Gk * Dh),
            1, jnp.asarray(tables), jnp.asarray(lengths))


@pytest.mark.parametrize("Gk, R, scale", [
    (2, 4, None),    # a group smaller than a sublane tile (padded to one), the scale a head's own
    (8, 4, 0.05),    # Granite 4.0-H: 4 queries a group, 8 groups, a scale that is not Dh ** -0.5 (0.25 here)
    (2, 16, None),   # Nemotron-H: a group that is a whole bf16 tile, as the kernel first took it
], ids=str)
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 3e-2)])
def test_gqa_paged_decode_is_reference_decode_attention(dtype, tol, Gk, R, scale):
    """Kernel (interpret mode; 3 compute blocks a full lane) and gather
    fallback against ``reference_decode_attention`` on a contiguous
    context with each K/V head repeated for its query heads."""
    B, Dh, bs, pages = 4, 16, 8, 150
    q, ks, vs, kp, vp, layer, tables, lengths = args = _paged(B, Gk, R, Dh, bs, pages, dtype)
    C = pages * bs
    idx = (tables[:, :, None] * bs + jnp.arange(bs)).reshape(B, C)
    rep = lambda v, axis: jnp.repeat(v, R, axis=axis)  # noqa: E731
    # the reference scales by Dh ** -0.5: hand it queries that carry the rest
    q_ref = q if scale is None else (q.astype(jnp.float32) * (scale * Dh ** 0.5))
    ctx = lambda pool: rep(pool[layer][idx].reshape(B, C, Gk, Dh), 2).astype(q_ref.dtype)  # noqa: E731
    want = reference_decode_attention(
        q_ref.reshape(B, Gk * R, Dh), rep(ks, 1).astype(q_ref.dtype), rep(vs, 1).astype(q_ref.dtype),
        ctx(kp), ctx(vp), jnp.arange(C)[None, :] < lengths[:, None])
    want = np.asarray(want, np.float32).reshape(B, Gk, R, Dh)
    got = gqa_paged_decode_attention_kernel(*args, block_size=bs, scale=scale, interpret=True)
    assert got.shape == q.shape and got.dtype == dtype and np.abs(np.asarray(got, np.float32) - want).max() < tol
    fallback = gqa_paged_decode_attention(*args, block_size=bs, scale=scale)
    assert np.abs(np.asarray(fallback, np.float32) - want).max() < tol
    if scale is not None:  # and the scale is read: without it the kernel says something else
        plain = gqa_paged_decode_attention_kernel(*args, block_size=bs, interpret=True)
        assert np.abs(np.asarray(plain, np.float32) - want).max() > 3 * tol


def test_gqa_paged_decode_does_not_depend_on_which_pages_a_lane_holds():
    B, Gk, R, Dh, bs, pages = 3, 2, 4, 16, 8, 70
    q, ks, vs, kp, vp, layer, tables, lengths = _paged(B, Gk, R, Dh, bs, pages, jnp.float32, seed=4)
    got = gqa_paged_decode_attention_kernel(q, ks, vs, kp, vp, layer, tables, lengths, block_size=bs, interpret=True)
    # the same rows under another assignment of physical pages
    perm = np.random.default_rng(9).permutation(np.arange(1, B * pages + 1))
    new_tables = perm[np.asarray(tables) - 1].astype(np.int32)
    rows = lambda t: (np.asarray(t)[:, :, None] * bs + np.arange(bs)).reshape(-1)  # noqa: E731
    kp2 = jnp.zeros_like(kp).at[:, rows(new_tables)].set(kp[:, rows(tables)])
    vp2 = jnp.zeros_like(vp).at[:, rows(new_tables)].set(vp[:, rows(tables)])
    again = gqa_paged_decode_attention_kernel(q, ks, vs, kp2, vp2, layer, jnp.asarray(new_tables), lengths,
                                              block_size=bs, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(again))
