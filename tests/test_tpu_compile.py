"""The device path's kernels compile for a TPU v5e at real widths.

Nothing runs here: each test lowers and compiles one kernel for a v5e chip
that is described, not attached, with the TPU lanes forced by
``segment.platform_hint("tpu")``. What the chip's compiler would refuse
(tiling, memory, an op it cannot lower) fails here, at no chip time.

The topology is described inside a module fixture and never while a
module is imported: only one process may load the TPU library, and every
pytest-xdist worker must collect the same tests. Keep these tests in this
one file, so that one worker loads the library for all of them.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from pixie_tpu.ops import countmin, hll, segment, tdigest


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    if saved_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


def compile_tpu(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip at ``(shape, dtype)`` args;
    returns (compiled, lanes the trace chose)."""
    args = [
        jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes
    ]
    segment.reduce_lanes(reset=True)
    with segment.platform_hint("tpu"):
        compiled = jax.jit(fn).lower(*args).compile()
    return compiled, segment.reduce_lanes(reset=True)


def test_entry_fold_block(one_chip):
    """``__graft_entry__.entry()``: seg_count + seg_sum + histogram
    update, the service_stats fold block, at 2^21 rows."""
    import __graft_entry__

    fn, _ = __graft_entry__.entry()
    n = 1 << 21
    compiled, _ = compile_tpu(
        fn,
        one_chip,
        ((n,), jnp.float64),
        ((n,), jnp.int64),
        ((n,), jnp.int32),
        ((n,), jnp.bool_),
    )
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_hll_sort_compact_lane(one_chip):
    n, groups = segment.SORTED_MIN_ROWS, 16
    state = hll.init(groups)

    def fn(gids, values, mask):
        return hll.update(state, gids, values, mask)

    _, lanes = compile_tpu(
        fn,
        one_chip,
        ((n,), jnp.int32),
        ((n,), jnp.int64),
        ((n,), jnp.bool_),
    )
    assert lanes.get("hll_sorted_compact") == 1, lanes


def test_merge_join_pairs(one_chip):
    nb, npr = 1 << 12, 1 << 22

    def fn(build_keys, probe_keys):
        order = jnp.argsort(build_keys, stable=True).astype(jnp.int32)
        return segment.merge_join_pairs(
            build_keys[order], order, probe_keys, npr
        )

    compile_tpu(fn, one_chip, ((nb,), jnp.int32), ((npr,), jnp.int32))


@pytest.mark.parametrize(
    "sketch", [tdigest, countmin], ids=["tdigest", "countmin"]
)
def test_sketch_update(one_chip, sketch):
    n, groups = 1 << 14, 16
    state = sketch.init(groups)

    def fn(gids, values, mask):
        return sketch.update(state, gids, values, mask)

    value_dtype = jnp.float64 if sketch is tdigest else jnp.int64
    compile_tpu(
        fn,
        one_chip,
        ((n,), jnp.int32),
        ((n,), value_dtype),
        ((n,), jnp.bool_),
    )


def test_sum_sorted_lane(one_chip):
    """The f64 error sum of one http_node.history fold block: 2^21 rows
    into 32,768 segments, above the MXU lane, takes the sorted lane: one
    sort, in 2^17-row tiles, that carries the emulated f64 values (the
    chip cannot bitcast them), and no scatter but the tiles' int32
    segment counts."""
    n, nseg = 1 << 21, 32768

    def fn(failures, gids, mask):
        return segment.seg_sum(failures, gids, nseg, mask)

    compiled, lanes = compile_tpu(
        fn,
        one_chip,
        ((n,), jnp.float64),
        ((n,), jnp.int32),
        ((n,), jnp.bool_),
    )
    assert lanes.get("sum_sorted") == 1, lanes
    hlo = compiled.as_text().splitlines()
    sorts = [ln for ln in hlo if " sort(" in ln]
    assert len(sorts) == 1 and "[16,131072]" in sorts[0], sorts
    scatters = [ln for ln in hlo if " scatter(" in ln]
    assert scatters and all("= s32[" in ln for ln in scatters), scatters
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
