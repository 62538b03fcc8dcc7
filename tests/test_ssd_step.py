"""``ops/ssd.ssd_step``'s tile: how many heads one grid step moves
(``heads_per_step``, a pure function of the shapes the call sees and ONE module
constant), and the kernel where one grid step spans SEVERAL groups of B and C
(interpret mode) against its XLA twin and the token-by-token recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

MB = 1 << 20

# (H, G, P, N) -> heads a grid step, groups a block at the module's budget (1 MB)
GEOMETRIES = {
    "falcon-h1-34b": ((32, 2, 128, 256), 8, 1),                  # 128 KB a head: half a group of 16
    "nemotron-3-super": ((128, 8, 64, 128), 32, 2),              # 32 KB a head: two groups of 16
    "granite-4.0-h-small": ((128, 1, 64, 128), 32, 1),           # a quarter of the one group
    "tiny": ((4, 2, 8, 16), 4, 2),                               # everything fits: all heads, both groups
}


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_heads_a_grid_step_at_the_cells_geometries(name):
    from dllama_tpu.ops import ssd

    (H, G, P, N), heads, groups = GEOMETRIES[name]
    hb = ssd.heads_per_step(H, G, P, N)
    assert ssd._STATE_BLOCK_BYTES == MB
    assert hb == heads and max(1, hb // (H // G)) == groups
    assert H % hb == 0 and hb * P * N * 4 <= ssd._STATE_BLOCK_BYTES
    # part of ONE group or WHOLE groups, never parts of two
    assert (H // G) % hb == 0 or hb % (H // G) == 0
    # and no admissible count is larger
    assert not [c for c in range(hb + 1, H + 1) if H % c == 0 and c * P * N * 4 <= MB
                and ((H // G) % c == 0 or c % (H // G) == 0)]


@pytest.mark.parametrize("shape, budget, heads", [
    ((32, 2, 128, 256), 2 * MB, 16),       # falcon's at 2 MB: one whole group
    ((128, 8, 64, 128), 2 * MB, 64),       # nemotron's at 2 MB: four groups
    ((128, 8, 64, 128), MB // 4, 8),       # ... and at the 256 KB the fixed list of 8 gave
    ((48, 3, 64, 128), MB, 16),            # 32 would fit and does not divide 48: one group of 16
    ((24, 2, 64, 128), MB, 24),            # groups of 12: 24 is two whole groups
    ((24, 2, 64, 128), MB // 2, 12),       # 16 heads fit: 12 is the largest that is no part of two groups
    ((6, 1, 64, 128), 5 * 32768, 3),       # 5 heads fit, 4 and 5 do not divide 6
    ((8, 2, 512, 1024), MB, 1),            # ONE head's state is over the budget: a head a grid step
])
def test_heads_a_grid_step_follow_the_budget_and_the_groups(shape, budget, heads, monkeypatch):
    from dllama_tpu.ops import ssd

    monkeypatch.setattr(ssd, "_STATE_BLOCK_BYTES", budget)
    assert ssd.heads_per_step(*shape) == heads


# 16 heads of 8 x 16 (512 B a head) in 4 groups of 4, under a budget of ``heads`` heads: part of a
# group, one whole group, two groups, all four
@pytest.mark.parametrize("heads, groups", [(2, 1), (4, 1), (8, 2), (16, 4)])
def test_a_grid_step_over_several_groups_is_the_recurrence(heads, groups, monkeypatch):
    """The kernel (interpret mode) where one grid step holds ``groups`` groups'
    B and C, on layer 1 of 3, two dead slots on the null row beside two live
    ones: the live rows' readout and state against the XLA twin and against
    ``ssd_recurrent`` to 1e-5, every cell of the pool that no live row names but
    the null row's keeps its bits."""
    from dllama_tpu.ops import ssd

    B, H, P, G, N = 4, 16, 8, 4, 16
    monkeypatch.setattr(ssd, "_STATE_BLOCK_BYTES", heads * P * N * 4)
    assert ssd.heads_per_step(H, G, P, N) == heads
    rng = np.random.default_rng(59)
    f = lambda *shape, scale=1.0: jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)
    x, Bm, Cm = f(B, 1, H, P), f(B, 1, G, N), f(B, 1, G, N)
    dt = jax.nn.softplus(f(B, 1, H))
    A = -jnp.exp(f(H, scale=0.5))
    pool = f(3, 6, H, P, N)                                  # [layers, rows (the null row first), H, P, N]
    rows = jnp.asarray([3, 0, 5, 0], jnp.int32)              # slots 1 and 3 are dead: they name the null row
    live = np.asarray(rows) != 0
    args = (pool, jnp.int32(1), rows, x[:, 0], dt[:, 0], jnp.exp(dt[:, 0] * A), Bm[:, 0], Cm[:, 0])
    y_ref, S_ref = ssd.ssd_recurrent(x, dt, A, Bm, Cm, pool[1][rows])
    y_xla, pool_xla = ssd.ssd_step_xla(*args)
    # the jitted entry's cache knows nothing of the patched constant: trace the function itself
    y_k, pool_k = ssd.ssd_step.__wrapped__(*args, interpret=True)
    for y, out in ((y_xla, pool_xla), (y_k, pool_k)):
        np.testing.assert_allclose(np.asarray(y)[live], np.asarray(y_ref[:, 0])[live], atol=1e-5)
        np.testing.assert_allclose(np.asarray(out[1][rows])[live], np.asarray(S_ref)[live], atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_k)[live], np.asarray(y_xla)[live], atol=1e-5)
    np.testing.assert_allclose(np.asarray(pool_k[:, 1:]), np.asarray(pool_xla[:, 1:]), atol=1e-5)
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, [0, 3, 5]] = False
    np.testing.assert_array_equal(np.asarray(pool_k)[untouched], np.asarray(pool)[untouched])


def test_the_sweep_tool_walks_its_two_tables_off_the_chip():
    """``tools/state_step_sweep.py --rehearse``: the chip's control flow at toy
    shapes in interpret mode (every admissible width traced at ITS width, the
    module's choice marked, parity held); nothing is timed."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run = subprocess.run([sys.executable, os.path.join(repo, "tools", "state_step_sweep.py"), "--rehearse"],
                         capture_output=True, text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert run.returncode == 0, run.stdout + run.stderr
    out = run.stdout
    assert [ln.split()[2] for ln in out.splitlines() if ln.startswith("  toy ")][:5] == ["1", "2", "4", "8", "16*"]
    assert "parity toy hb 16" in out and "PASS" in out and "FAIL" not in out
    assert out.count("not measured") == 2 * (5 + 4 + 4) and "toy-kda   hb   6*" in out
