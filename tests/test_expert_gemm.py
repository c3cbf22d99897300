"""The dropless grouped int8 GEMM (``kernels/expert_gemm``) and the gated
product between its calls (``silumul`` told the live rows) against their
XLA runners, bit for bit, on the dispatch layouts an expert layer produces."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import silumul, silumul_ref
from repro.kernels.expert_gemm import expert_gemm, expert_gemm_ref
from repro.models import moe
from repro.quant.qparams import np_quantize_multiplier, requantize

E, D, F, K = 6, 128, 256, 2
SCALES = (0.05, 0.01, 0.05)


def _ids(case: str, rows: int, rng) -> np.ndarray:
    ids = rng.integers(0, E, size=(rows, K))
    if case == "one-expert":
        ids[:] = 4
        ids[:, 1] = 4  # both of a row's picks land in one group
    elif case == "empty-experts":
        ids = np.where(ids % 3 == 0, 1, ids)  # experts 0 and 3 get no rows
    elif case == "parked-rows":
        ids[::3] = E  # every third row carries no request
    elif case == "all-parked":
        ids[:] = E
    return ids


@pytest.mark.parametrize("case,rows", [
    ("one-expert", 40), ("empty-experts", 40), ("parked-rows", 40),
    ("ragged-groups", 23), ("all-parked", 8),
])
@pytest.mark.parametrize("n", [F, D], ids=["up", "down"])
def test_kernel_is_bit_exact_against_the_xla_runner(case, rows, n):
    rng = np.random.default_rng(rows)
    k_dim = D if n == F else F
    h = jnp.asarray(rng.integers(-127, 128, (rows, k_dim)), jnp.int8)
    ids = jnp.asarray(_ids(case, rows, rng), jnp.int32)
    w = jnp.asarray(rng.integers(-127, 128, (E, k_dim, n)), jnp.int8)
    x, dest, tiles = moe.dispatch(h, ids, E, moe.ROW_TILE)
    got = np.asarray(expert_gemm(x, tiles, w, scales=SCALES, row_tile=moe.ROW_TILE))
    want = np.asarray(expert_gemm_ref(x, tiles, w, scales=SCALES, row_tile=moe.ROW_TILE))
    np.testing.assert_array_equal(got, want)
    # every routed pair's row is its own expert's product; the rest are 0
    mult, shift = np_quantize_multiplier(SCALES[0] * SCALES[1] / SCALES[2])
    dn, idn = np.asarray(dest), np.asarray(ids)
    seen = np.zeros(got.shape[0], bool)
    for r in range(rows):
        for j in range(K):
            if idn[r, j] < E:
                acc = np.asarray(h[r], np.int64) @ np.asarray(w[idn[r, j]], np.int64)
                row = requantize(jnp.asarray(acc, jnp.int32), int(mult), int(shift))
                np.testing.assert_array_equal(got[dn[r, j]], np.asarray(row))
                seen[dn[r, j]] = True
            else:
                assert dn[r, j] == got.shape[0]
    assert not got[~seen].any()


@pytest.mark.parametrize("case,rows", [
    ("one-expert", 40), ("empty-experts", 40), ("parked-rows", 40),
    ("ragged-groups", 23), ("all-parked", 8),
])
def test_silumul_skips_the_rows_past_the_live_tiles(case, rows):
    rng = np.random.default_rng(rows + 1)
    ids = jnp.asarray(_ids(case, rows, rng), jnp.int32)
    _, _, tiles = moe.dispatch(jnp.zeros((rows, D), jnp.int8), ids, E, moe.ROW_TILE)
    p = moe.grouped_rows(rows * K, E, moe.ROW_TILE)[1]
    # codes on every row, padding included: the dead rows must still read 0
    g = jnp.asarray(rng.integers(-128, 128, (p, F)), jnp.int8)
    u = jnp.asarray(rng.integers(-128, 128, (p, F)), jnp.int8)
    live = moe.live_rows(tiles, moe.ROW_TILE)
    got = np.asarray(silumul(g, u, scales=SCALES, live_rows=live))
    np.testing.assert_array_equal(got, np.asarray(silumul_ref(g, u, scales=SCALES,
                                                              live_rows=live)))
    n = int(live)
    assert not got[n:].any()
    np.testing.assert_array_equal(got[:n], np.asarray(silumul_ref(g, u, scales=SCALES))[:n])


def test_tile_map_counts_and_bounds():
    rng = np.random.default_rng(5)
    ids = jnp.asarray(_ids("empty-experts", 40, rng), jnp.int32)
    h = jnp.zeros((40, D), jnp.int8)
    x, _, tiles = moe.dispatch(h, ids, E, moe.ROW_TILE)
    t, p = moe.grouped_rows(40 * K, E, moe.ROW_TILE)
    assert x.shape == (p, D) and tiles.shape == (t + 3,)
    counts = np.bincount(np.asarray(ids).ravel(), minlength=E)
    live = int(sum(-(-c // moe.ROW_TILE) for c in counts))
    tile_e = np.asarray(tiles[:t])
    assert int(tiles[t]) == live and int(tiles[t + 1]) == 80
    assert int(tiles[t + 2]) == int((counts > 0).sum()) == E - 2
    # live tiles walk the experts in order; the tiles after repeat the last
    assert list(tile_e[:live]) == sorted(tile_e[:live])
    assert (tile_e[live:] == tile_e[live - 1]).all()
