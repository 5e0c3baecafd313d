"""Real-width compiles of the served Pallas kernels for a described v5e chip.

Interpret mode accepts block shapes and VMEM footprints the TPU compiler
refuses, so every main-path kernel is compiled here with the chip's own
compiler, at the sketch sizes the benchmark serves (n = 256 and n = 1024,
the largest one `rank_transform` documents), for a chip that is described,
not attached. Nothing runs: a compile that passes says the kernel tiles
and fits, not that it is fast or right (the interpret-mode tests pin the
results). Each assertion requires ``tpu_custom_call`` in the compiled
program, so a kernel that silently fell back to XLA fails.

The topology is described inside a fixture — never at import — so every
test worker collects the same tests and only the one running this file
loads the TPU compiler.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import candidates as CD
from repro.engine import plans as PL
from repro.engine.index import IndexShard
from repro.kernels import containment as CT
from repro.kernels import postings as PM
from repro.kernels import rank_transform as RT
from repro.kernels import sketch_join as SJ

SIZES = (256, 1024)
#: candidate block per query batch, as `serve._SegmentExec.chunk_for` picks
CHUNK = {1: 512, 8: 512, 32: 128}
f32, u32, i32 = jnp.float32, jnp.uint32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel lowered without Pallas"
    assert compiled.memory_analysis() is not None
    return text


@pytest.mark.parametrize("n", SIZES)
def test_sketch_join_single(one_chip, n):
    _compile(SJ.sketch_join_moments,
             [((n,), u32), ((n,), f32), ((n,), f32),
              ((512, n), u32), ((512, n), f32), ((512, n), f32)], one_chip)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("B", (1, 8, 32))
def test_sketch_join_batched(one_chip, n, B):
    C = CHUNK[B]
    fn = jax.vmap(SJ.sketch_join_moments, in_axes=(0, 0, 0, None, None, None))
    _compile(fn, [((B, n), u32), ((B, n), f32), ((B, n), f32),
                  ((C, n), u32), ((C, n), f32), ((C, n), f32)], one_chip)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("B", (1, 8, 32))
def test_containment_batched(one_chip, n, B):
    C = CHUNK[B]
    fn = jax.vmap(CT.containment_hits, in_axes=(0, 0, None, None))
    _compile(fn, [((B, n), u32), ((B, n), f32), ((C, n), u32),
                  ((C, n), f32)], one_chip)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("W", (8, 16))
@pytest.mark.parametrize("B", (1, 8, 32))
def test_postings_merge(one_chip, n, W, B):
    _compile(PM.postings_merge, [((B, n * W), i32)], one_chip)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("W", (8, 16))
@pytest.mark.parametrize("B", (1, 8, 32))
def test_postings_select(one_chip, n, W, B):
    fn = lambda c, k, fl: PM.postings_select(c, k, fl, 1024)
    _compile(fn, [((B, n * W), i32), ((B, n * W), f32), ((), f32)],
             one_chip)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("kind", ("spearman", "rin"))
def test_rank_moments(one_chip, n, kind):
    # 4,096 rows, the scan's bucket-8 block (8 × 512 candidates): the
    # scalar-prefetched occupancy words (one per 8 rows) fill 2 KB of SMEM
    fn = lambda a, b, m: RT.rank_moments(a, b, m, kind=kind)
    _compile(fn, [((32 * 128, n), f32)] * 3, one_chip)


@pytest.mark.parametrize("n", SIZES)
def test_qn_correlation(one_chip, n):
    _compile(RT.qn_correlation, [((32 * 128, n), f32)] * 3, one_chip)


def _plan_args(mesh, C, n, B):
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    col = NamedSharding(mesh, P("shard"))
    S = lambda s, dt, sh=rep: jax.ShapeDtypeStruct(s, dt, sharding=sh)
    qa = (S((B, n), u32), S((B, n), f32), S((B, n), f32), S((B,), f32),
          S((B,), f32))
    shard = IndexShard(key_hash=S((C, n), u32, col),
                       values=S((C, n), f32, col), mask=S((C, n), f32, col),
                       col_min=S((C,), f32, col), col_max=S((C,), f32, col),
                       rows=S((C,), f32, col))
    return qa, shard, S((4,), f32), S


def _kernel_names(text):
    return {name for line in text.splitlines() if "tpu_custom_call" in line
            for name in re.findall(r"jit\((\w+)\)\)?/pallas_call", line)}


@pytest.mark.parametrize("ndev", (1, 4))
def test_served_plans_resolve_to_pallas(topo, ndev):
    """On a TPU mesh — one chip, or the 2x2 host column-sharded — the
    default policy resolves to the Pallas kernels, and the scan plan, the
    fused inverted plan and the inverted probe compile with every served
    kernel in them, the estimator branches included."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(topo.devices[:ndev]), ("shard",))
    C, n, B, M, W = 8192, 256, 8, 1024, 8
    shape = PL.resolve_shape(PL.ShapePolicy(), mesh)
    assert shape.kernels.backend == "pallas"
    assert shape.combine == ("host" if ndev > 1 else "gather")
    qa, shard, ops, S = _plan_args(mesh, C, n, B)
    scan = PL.make_scan_fn(mesh, C, n, shape, batch=B)
    text = scan.lower(*qa, shard, ops).compile().as_text()
    assert _kernel_names(text) >= {"sketch_join_moments", "rank_moments",
                                   "qn_correlation"}
    E = C * n
    inv = PL.make_inverted_fn(mesh, C, n, shape, M, E, W, batch=B)
    text = inv.lower(*qa, shard, S((E,), u32), S((E,), i32),
                     ops).compile().as_text()
    assert _kernel_names(text) >= {"postings_merge", "postings_select",
                                   "sketch_join_moments", "rank_moments",
                                   "qn_correlation"}
    # the standalone inverted probe (topm candidates, search_joinable):
    # on a mesh the compiler refuses to partition a bare Pallas call
    probe = CD.make_postings_probe_fn(E, W, B, n, shape.kernels, mesh)
    text = probe.lower(qa[0], qa[2], S((E,), u32),
                       S((E,), i32)).compile().as_text()
    assert _kernel_names(text) >= {"postings_merge"}
