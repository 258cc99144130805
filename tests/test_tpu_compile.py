"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described, not attached.  These compiles catch what interpret mode
cannot (unsupported gathers, 64-bit types in a kernel body, VMEM limits)
at the widths ``chip_smoke.py`` serves: soc-Slashdot0811 at full scale.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.plan import MIN_WIDTH, executor_geometry
from repro.core.vlftj import _expand_level
from repro.core.yannakakis import _spmv
from repro.kernels import ops as kops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.intersect_bitset import (bitset_intersect_count_pallas,
                                            bitset_member_count_pallas)

# soc-Slashdot0811 at scale 1.0 (make_snap_like(..., seed=0))
N_NODES = 77_360
N_EDGES = 1_778_854
MAX_DEGREE = 1_577
N_WORDS = (N_NODES + 31) // 32
N_HUBS = 4096          # bitset rows: hubs of degree >= n/1024
WIDTH, _ = executor_geometry(MAX_DEGREE)          # 2048
N_ITER = math.ceil(math.log2(MAX_DEGREE)) + 1     # GraphDB.bsearch_iters


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("check_mode,count_only,width", [
    ("bsearch", True, WIDTH), ("bsearch", False, WIDTH),
    ("bsearch", True, MIN_WIDTH), ("bsearch", False, MIN_WIDTH),
    ("tile", True, WIDTH), ("bitset", True, WIDTH)])
def test_expand_level_compiles(one_chip, check_mode, count_only, width):
    """One 3-clique level (probe both bound columns, c > b) at the widest
    and the narrowest width class the smoke dispatches, each at its chunk
    size, with the package's x64 counts."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    _, chunk = executor_geometry(width, width=width)
    kw = dict(probe_cols=(0, 1), n_unary=0, lower_cols=(1,), upper_cols=(),
              width=width, n_iter=N_ITER, count_only=count_only,
              needs_degree=False, check_mode=check_mode,
              check_width=512 if check_mode == "tile" else 0)
    bitset = {}
    if check_mode == "bitset":
        bitset = dict(rep_tag=s((N_NODES,), jnp.int32),
                      bitset_words=s((N_HUBS, N_WORDS), jnp.uint32))

    def step(indptr, indices, frontier, mult, row_valid, **bs):
        return _expand_level(indptr, indices, (), frontier, mult, row_valid,
                             **bs, **kw)

    compiled = jax.jit(step).lower(
        s((N_NODES + 1,), jnp.int32), s((N_EDGES,), jnp.int32),
        s((chunk, 2), jnp.int32), s((chunk,), jnp.int64),
        s((chunk,), jnp.bool_), **bitset).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 << 30


def test_spmv_compiles_without_a_scatter(one_chip):
    """Counting Yannakakis' SpMV over the whole edge array, with int64
    messages: the TPU's compiler keeps it free of scatters."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    compiled = _spmv.lower(
        s((N_NODES + 1,), jnp.int32), s((N_EDGES,), jnp.int32),
        s((N_EDGES,), jnp.int32), s((N_NODES,), jnp.int64),
        num_segments=N_NODES).compile()
    assert " scatter(" not in compiled.as_text()


def test_bitset_intersect_kernel_compiles(one_chip):
    words = -(-N_WORDS // 128) * 128
    s = _spec(one_chip, (64, words), jnp.uint32)
    compiled = jax.jit(lambda a, b: bitset_intersect_count_pallas(
        a, b, interpret=False)).lower(s, s).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bitset_member_kernel_compiles(one_chip):
    compiled = jax.jit(lambda w, b, n: bitset_member_count_pallas(
        w, b, n, interpret=False)).lower(
        _spec(one_chip, (64, N_WORDS), jnp.uint32),
        _spec(one_chip, (64, WIDTH), jnp.int32),
        _spec(one_chip, (64,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_compiles(one_chip):
    q = _spec(one_chip, (1, 8, 1024, 128), jnp.bfloat16)
    kv = _spec(one_chip, (1, 2, 1024, 128), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, interpret=False)).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ops_refuses_searchsorted_kernel_on_tpu(monkeypatch):
    """The binary-search kernel's whole-array gather does not lower for
    the TPU: asking for it there raises instead of interpreting it or
    quietly running the reference."""
    assert set(kops.TPU_REFUSED) == {"searchsorted_segments"}
    monkeypatch.setattr(kops, "_USE_PALLAS", True)
    monkeypatch.setattr(kops.jax, "default_backend", lambda: "tpu")
    x = jnp.zeros((8, 128), jnp.int32)
    with pytest.raises(NotImplementedError, match="searchsorted_segments"):
        kops.searchsorted_segments(jnp.arange(16, dtype=jnp.int32), x, x, x,
                                   n_iter=5)
