"""Compile the trainer-path Pallas kernels for a TPU v5e, without one.

The TPU compiler is installed with JAX; it compiles for a chip that is
described (``jax.experimental.topologies``) and not attached, and it
refuses what Mosaic cannot lower: block shapes off the (8, 128) tiling,
primitives it has no rule for, layouts it cannot cast, more VMEM than
a kernel may use. Interpret mode accepts all of those, so the parity
suites cannot catch them; these tests do, at no chip time.

Each test compiles with ``interpret=False`` and checks that the kernel
made it into the program as a Mosaic custom call. The fused steps are
compiled at the size ``chip_smoke.py`` runs them (phase B); the sampler
and store kernels at that run's widths. The topology is described only
inside a fixture, so importing this file never loads the TPU library.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_step, frontier_unique, gather_rows, score_update

# chip_smoke.py phase B: products at scale 1.0 over 4 partitions, batch
# 32, fanout (10, 25). C is the largest per-PE buffer, K = 2C the
# candidate cap, Mt = 32 * (1 + 10 + 250) the raw frontier width.
P, C, MT, N_NODES, F = 4, 1294, 8352, 24000, 100
K = 2 * C


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` → a ShapeDtypeStruct on one v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _assert_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _state(shape):
    return (
        shape((P, C)),
        shape((P, C), jnp.float32),
        shape((P, C), jnp.bool_),
        shape((P, C), jnp.bool_),
        shape((P, C), jnp.bool_),
    )


def test_fused_frontier_step_compiles(shape, no_persistent_cache):
    def step(ids, s, v, a, cap, aug, part_of, cand):
        return fused_step.fused_frontier_step_pallas(
            ids, s, v, a, cap, None, aug, part_of, cand, None, None, None,
            None, cand_cap=K, interpret=False,
        )

    _assert_mosaic(
        step, *_state(shape), shape((P, MT + 1)), shape((N_NODES,)),
        shape((P, K)),
    )


def test_fused_frontier_step_wide_compiles(shape, no_persistent_cache):
    def step(ids, ids_hi, s, v, a, cap, w, aug, part_of, cand, cand_hi, nw):
        return fused_step.fused_frontier_step_wide_pallas(
            ids, ids_hi, s, v, a, cap, w, aug, part_of, cand, cand_hi, nw,
            None, None, None, cand_cap=K, id_base=1 << 31, interpret=False,
        )

    ids, s, v, a, cap = _state(shape)
    _assert_mosaic(
        step, ids, shape((P, C)), s, v, a, cap, shape((P, C), jnp.float32),
        shape((P, 2 * MT + 1)), shape((N_NODES,)), shape((P, K)),
        shape((P, K)), shape((N_NODES,), jnp.float32),
    )


def test_fused_step_compiles(shape, no_persistent_cache):
    def step(ids, s, v, a, cap, q, cand, g1, g2, g3):
        return fused_step.fused_step_pallas(
            ids, s, v, a, cap, None, q, cand, None, g1, g2, g3,
            interpret=False,
        )

    gate = shape((P,), jnp.bool_)
    _assert_mosaic(
        step, *_state(shape), shape((P, MT)), shape((P, K)), gate, gate, gate
    )


def test_frontier_unique_batch_compiles(shape, no_persistent_cache):
    def dedup(keys, remote):
        return frontier_unique.frontier_unique_batch(
            keys, remote, interpret=False
        )

    _assert_mosaic(dedup, shape((P, MT)), shape((P, MT), jnp.bool_))


def test_gather_rows_batch_compiles(shape, no_persistent_cache):
    def gather(tables, idx):
        return gather_rows.gather_rows_batch(tables, idx, interpret=False)

    n_max = N_NODES // P
    _assert_mosaic(gather, shape((P, n_max, F), jnp.float32), shape((P, K)))


def test_score_policy_update_batch_compiles(shape, no_persistent_cache):
    def score(s, a, w):
        return score_update.score_policy_update_batch(
            s, a, w, mode="capped", interpret=False
        )

    _assert_mosaic(
        score,
        shape((P, C), jnp.float32),
        shape((P, C), jnp.bool_),
        shape((P, C), jnp.float32),
    )


def test_sage_grads_gathers_from_the_table_in_place(shape, no_persistent_cache):
    """Training gathers its rows from the device feature table inside
    ``sage_grads``. The trainer pads the table's rows to whole lanes
    (``DistributedTrainer.feature_table``), so the chip lays it out row
    by row and the program reads it where it lies: no copy of the table
    (an unpadded F = 100 table is copied, once per block, every call)."""
    from repro.gnn.sage import Rows, init_sage, sage_grads
    from repro.gnn.train import LANES

    width = -(-F // LANES) * LANES
    params = jax.tree_util.tree_map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: init_sage(jax.random.PRNGKey(0), F, 256, 47)),
    )
    table = shape((N_NODES, width), jnp.float32)
    rows = [Rows(table, None, shape(dims), F) for dims in [(32,), (32, 10), (32, 10, 25)]]
    hlo = sage_grads.lower(params, *rows, shape((32,))).compile().as_text()
    assert not [
        line for line in hlo.splitlines()
        if f"[{N_NODES},{width}]" in line and " copy(" in line
    ]


def test_gat_grads_gathers_from_the_table_in_place(shape, no_persistent_cache):
    """``gat_grads`` reads each hop's rows fanout-major from the same
    lane-padded table (``Rows.fanout_major``): no copy of the table, at
    the published widths (F = 100, 4 heads x 128, 47 classes) and the
    ``products-gat`` job's fanout [10, 10, 10]."""
    from repro.gnn.gat import gat_grads, init_gat
    from repro.gnn.sage import Rows
    from repro.gnn.train import LANES

    width = -(-F // LANES) * LANES
    params = jax.tree_util.tree_map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: init_gat(jax.random.PRNGKey(0), F, 128, 47)),
    )
    table = shape((N_NODES, width), jnp.float32)
    dims = [(32,), (32, 10), (32, 10, 10), (32, 10, 10, 10)]
    rows = [Rows(table, None, shape(d), F) for d in dims]
    hlo = gat_grads.lower(params, rows, shape((32,))).compile().as_text()
    assert not [
        line for line in hlo.splitlines()
        if f"[{N_NODES},{width}]" in line and " copy(" in line
    ]


def test_gat_grads_never_projects_the_deepest_hop(shape, no_persistent_cache):
    """``gat_grads`` at the ``products-gat`` job's shapes (batch 512,
    fanout [10, 10, 10], F = 100, 4 heads x 128, 47 classes) scores and
    sums raw rows and projects each target's sum: no instruction holds
    the 512,000 deepest-hop rows widened to 4 x 128 channels."""
    from repro.gnn.gat import gat_grads, init_gat
    from repro.gnn.sage import Rows
    from repro.gnn.train import LANES

    b, heads, hidden = 512, 4, 128
    width = -(-F // LANES) * LANES
    params = jax.tree_util.tree_map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: init_gat(jax.random.PRNGKey(0), F, hidden, 47, heads)),
    )
    table = shape((N_NODES, width), jnp.float32)
    dims = [(b,), (b, 10), (b, 10, 10), (b, 10, 10, 10)]
    rows = [Rows(table, None, shape(d), F) for d in dims]
    hlo = gat_grads.lower(params, rows, shape((b,))).compile().as_text()
    deepest = b * 10 * 10 * 10
    widened = (f"[{deepest},{heads * hidden}]", f"[{deepest},{heads},{hidden}]")
    assert f"[{deepest}," in hlo
    assert not [line for line in hlo.splitlines() if any(s in line for s in widened)]
