"""The two state-space kernels (``paddle_tpu/ops/ssm.py``) against their
plain-XLA oracles and against the token-by-token recurrence in float32, in
interpret mode. Tolerances: everything here is float32 at the highest
matmul precision, so what separates kernel, oracle and recurrence is the
order of the sums (a 128-token piece sums 128 terms at once where the
recurrence folds them one by one): a few float32 ulps of the largest term,
1e-4 of the output's scale with room."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import _common, ssm

NH, P, N, G, L, SLOTS = 4, 16, 32, 2, 2, 5


@pytest.fixture(autouse=True)
def _interpret():
    with _common.interpret_mode(True):
        yield


def _inputs(c, seed=0, delta_a=None):
    """A chunk's inputs; ``delta_a`` fixes every token's ``delta A``."""
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    x, bm, cm = f(c, NH, P), f(c, G, N), f(c, G, N)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, NH), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (c, NH))),
                     jnp.float32)
    if delta_a is not None:
        dt = jnp.full((c, NH), delta_a, jnp.float32) / a[None]
    return x, dt, a, bm, cm


def _state(seed=1):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(L, SLOTS, NH, P, N), jnp.float32)


def _close(got, want, tol=1e-4):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(1.0, float(np.abs(want).max()))
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale)


def _live(dt, n_live):
    return jnp.where(jnp.arange(dt.shape[0])[:, None] < n_live, dt, 0.0)


@pytest.mark.parametrize("c,n_live", [(128, 1), (128, 127), (128, 128),
                                      (256, 129), (512, 512)])
def test_scan_matches_oracle_and_recurrence(c, n_live):
    x, dt, a, bm, cm = _inputs(c, seed=n_live)
    state = _state()
    s_in = state[1, 3]
    dt = _live(dt, n_live)
    y, new = ssm.ssd_chunk_scan(x, dt, a, bm, cm, state, 1, 3, 128, n_live)
    y_o, s_o = ssm.ssd_chunk_scan_xla(x, dt, a, bm, cm, s_in)
    y_r, s_r = ssm.ssm_recurrence(x[:n_live], dt[:n_live], a, bm[:n_live],
                                  cm[:n_live], s_in)
    _close(y[:n_live], y_o[:n_live])
    _close(y[:n_live], y_r)
    _close(new[1, 3], s_o)
    _close(new[1, 3], s_r)          # the state after the LAST LIVE token
    # every other slot and layer is untouched, bit for bit
    keep = np.ones((L, SLOTS), bool)
    keep[1, 3] = False
    np.testing.assert_array_equal(np.asarray(new)[keep],
                                  np.asarray(state)[keep])


def test_first_chunk_starts_from_zeros_whatever_the_slot_holds():
    x, dt, a, bm, cm = _inputs(128, seed=5)
    y, new = ssm.ssd_chunk_scan(x, dt, a, bm, cm, _state(), 0, 2, 0, 128)
    y_r, s_r = ssm.ssm_recurrence(x, dt, a, bm, cm, jnp.zeros((NH, P, N)))
    _close(y, y_r)
    _close(new[0, 2], s_r)


def test_state_carried_over_three_chunks_equals_one_pass():
    x, dt, a, bm, cm = _inputs(384, seed=7)
    state = _state()
    ys = []
    for k, n_live in enumerate((128, 128, 77)):
        sl = slice(128 * k, 128 * (k + 1))
        y, state = ssm.ssd_chunk_scan(
            x[sl], _live(dt[sl], n_live), a, bm[sl], cm[sl], state, 1, 4,
            128 * k, n_live)
        ys.append(y[:n_live])
    n = 128 + 128 + 77
    y_r, s_r = ssm.ssm_recurrence(x[:n], dt[:n], a, bm[:n], cm[:n],
                                  jnp.zeros((NH, P, N)))
    _close(jnp.concatenate(ys), y_r)
    _close(state[1, 4], s_r)


@pytest.mark.parametrize("delta_a", [-0.001, -30.0])
def test_scan_decay_near_one_and_near_zero(delta_a):
    """``exp(L_t - L_s)`` over a 128-token piece: with ``delta A`` = -0.001
    the state barely decays, with -30 a token is forgotten at once and
    ``L`` reaches -3840, whose plain exponential underflows and whose
    negation would overflow; neither may leave a non-finite number."""
    x, dt, a, bm, cm = _inputs(128, seed=9, delta_a=delta_a)
    state = _state()
    y, new = ssm.ssd_chunk_scan(x, dt, a, bm, cm, state, 0, 1, 128, 128)
    y_r, s_r = ssm.ssm_recurrence(x, dt, a, bm, cm, state[0, 1])
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(new)).all()
    _close(y, y_r)
    _close(new[0, 1], s_r)


def test_update_matches_oracle_and_recurrence_in_place():
    b = 4
    x, dt, a, bm, cm = _inputs(b, seed=11)
    state = _state()
    slots = jnp.asarray([2, 4, 1, 0], jnp.int32)    # the last row is padding
    xdt, da = dt[:, :, None] * x, jnp.exp(dt * a[None])
    y, new = ssm.ssm_state_update(xdt, da, bm, cm, state, 1, slots)
    y_o, s_o = ssm.ssm_state_update_xla(xdt, da, bm, cm, state[1, slots])
    _close(y, y_o, 1e-5)
    for r in range(3):
        y_r, s_r = ssm.ssm_recurrence(x[r:r + 1], dt[r:r + 1], a,
                                      bm[r:r + 1], cm[r:r + 1],
                                      state[1, slots[r]])
        _close(y[r], y_r[0], 1e-5)
        _close(new[1, slots[r]], s_r, 1e-5)
        _close(new[1, slots[r]], s_o[r], 1e-5)
    # a padded row writes the null slot 0 and leaves its neighbours alone:
    # slot 3 (no row's) and the whole of layer 0 are bit for bit what they
    # were
    np.testing.assert_array_equal(np.asarray(new[1, 3]),
                                  np.asarray(state[1, 3]))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))


def test_update_then_scan_agree_on_one_token():
    """A token fed by the decode kernel moves the state as the same token
    fed as a one-token chunk does."""
    x, dt, a, bm, cm = _inputs(128, seed=13)
    state = _state()
    _, by_scan = ssm.ssd_chunk_scan(x, _live(dt, 1), a, bm, cm, state, 0, 2,
                                    128, 1)
    xdt, da = dt[:1, :, None] * x[:1], jnp.exp(dt[:1] * a[None])
    _, by_update = ssm.ssm_state_update(xdt, da, bm[:1], cm[:1], state, 0,
                                        jnp.asarray([2], jnp.int32))
    _close(by_scan[0, 2], by_update[0, 2], 1e-5)


def test_counted_work_by_hand():
    # 128 tokens, 4 heads of 16 with a state of 32, 2 groups:
    # C B^T 2 x 128 x 32 a group; per head 128 x 16 + 2 x 16 x 32
    assert ssm.ssd_scan_flops(128, 4, 16, 32, 2) == 2 * 128 * (
        2 * 128 * 32 + 4 * (128 * 16 + 2 * 16 * 32))
    # one row: its state in and out, x and y in f32, the decay, B and C
    assert ssm.ssm_update_bytes(1, 4, 16, 32, 2, 2) == (
        2 * 4 * 16 * 32 * 4 + 2 * 4 * 16 * 4 + 4 * 4 + 2 * 2 * 32 * 2)


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
