"""``grouped_matmul_live``: the serving path's forward-only grouped matmul,
whose grid walks the live tiles alone (interpreter, tiny shapes)."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.ops.grouped_matmul import (grouped_matmul, grouped_matmul_live,
                                           tile_schedule)

TILE, K, N, E = 16, 32, 128, 4


def _case(counts, n_tiles):
    rng = np.random.default_rng(sum(counts))
    counts = jnp.asarray(counts, jnp.int32)
    tile_e, live, first, last, offsets = tile_schedule(counts, n_tiles, TILE)
    x = jnp.asarray(rng.standard_normal((n_tiles * TILE, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
    return x, w, (tile_e, live, first, last), int(offsets[E]) // TILE


@pytest.mark.parametrize("counts", [[5, 0, 17, 1], [0, 0, 40, 0],
                                    [16, 16, 16, 16]])
def test_live_tiles_equal_the_static_grid(counts):
    x, w, sched, n_live = _case(counts, 8)
    want = np.asarray(grouped_matmul(x, w, sched, TILE))
    got = np.asarray(grouped_matmul_live(x, w, sched, jnp.int32(n_live),
                                         TILE))
    rows = n_live * TILE
    np.testing.assert_array_equal(got[:rows], want[:rows])
    # every live row is its expert's product
    tile_e = np.asarray(sched[0])
    for t in range(n_live):
        np.testing.assert_allclose(
            got[t * TILE:(t + 1) * TILE],
            np.asarray(x)[t * TILE:(t + 1) * TILE] @ np.asarray(w)[tile_e[t]],
            rtol=1e-5, atol=1e-5)


def test_no_live_tile_runs_no_step():
    """An empty grid: the call returns (its rows are never read)."""
    x, w, sched, n_live = _case([0, 0, 0, 0], 4)
    assert n_live == 0
    out = grouped_matmul_live(x, w, sched, jnp.int32(0), TILE)
    assert out.shape == (4 * TILE, N)
