"""The main path compiles for a TPU v5e at the widths users run.

Compiles (nothing runs) the five Pallas kernels of the solve and serve
path, and one local-backend outer iteration, for a described v5e chip:
at gisette's published dense shape (6,000 x 5,000) and real-sim's
(57,848 x 20,958 with ~2.9M nonzeros, k_max 276). Interpret-mode tests
cannot see what the chip's compiler refuses (block tiling, in-kernel
gathers, VMEM limits); these can. Each kernel must lower to a Mosaic
custom call, never to its XLA reference.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the worker that runs this file
keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import PCDNConfig
from repro.core.design_matrix import DenseDesign
from repro.core.problem import L1Problem
from repro.engine.local import LocalBackend
from repro.kernels import ops

GISETTE = dict(s=6000, n=5000)
REALSIM = dict(s=57848, n=20958, k_max=276)
P = 256                  # the CLIs' default bundle size (full scope)
P_SUPPORT = 48           # largest P whose support scope auto-selects
Q = 40                   # ArmijoParams.max_steps candidates
BATCH, MODELS, ACTIVE, REQ_KMAX = 64, 8, 2048, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", cache_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # kernels choose interpret mode at trace time from the process's own
    # (CPU) backend: compile them for the described chip instead, from
    # fresh traces
    interpret = ops.INTERPRET
    ops.INTERPRET = False
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    ops.INTERPRET = interpret
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


def _compiled_text(fn, sharding, *specs) -> str:
    return jax.jit(fn).lower(*_shapes(sharding, *specs)).compile().as_text()


f32, i32 = jnp.float32, jnp.int32


def _direction(n):
    s = GISETTE["s"]
    return (lambda XB, u, v, w: ops.pcdn_direction(XB, u, v, w),
            ((s, P), f32), ((s,), f32), ((s,), f32), ((P,), f32))


def _sparse_direction(n):
    s, K = REALSIM["s"], REALSIM["k_max"]
    return (lambda r, x, u, v, w: ops.pcdn_sparse_direction(r, x, u, v, w),
            ((P, K), i32), ((P, K), f32), ((s,), f32), ((s,), f32),
            ((P,), f32))


def _bundle(n):
    K = REALSIM["k_max"]
    R = P_SUPPORT * K
    return (lambda x, pos, z, y, w, a: ops.pcdn_bundle(x, pos, z, y, w, a,
                                                        4.0),
            ((P_SUPPORT, K), f32), ((P_SUPPORT, K), i32), ((R,), f32),
            ((R,), f32), ((P_SUPPORT,), f32), ((Q,), f32))


def _serve_dense(n):
    return (lambda X, i, v: ops.serve_margins_dense(X, i, v),
            ((BATCH, n), f32), ((MODELS, ACTIVE), i32),
            ((MODELS, ACTIVE), f32))


def _serve_csc(n):
    return (lambda r, x, i, v: ops.serve_margins_csc(r, x, i, v,
                                                     n_requests=BATCH),
            ((n, REQ_KMAX), i32), ((n, REQ_KMAX), f32),
            ((MODELS, ACTIVE), i32), ((MODELS, ACTIVE), f32))


@pytest.mark.parametrize("kernel,n", [
    (_direction, GISETTE["n"]),
    (_sparse_direction, REALSIM["n"]),
    (_bundle, REALSIM["n"]),
    (_serve_dense, GISETTE["n"]),
    (_serve_dense, REALSIM["n"]),
    (_serve_csc, GISETTE["n"]),
    (_serve_csc, REALSIM["n"]),
], ids=["direction-gisette", "sparse_direction-real-sim",
        "bundle-real-sim", "serve_dense-gisette", "serve_dense-real-sim",
        "serve_csc-gisette", "serve_csc-real-sim"])
def test_kernel_compiles_for_v5e(one_chip, kernel, n):
    fn, *specs = kernel(n)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *specs)


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["xla", "kernels"])
def test_local_outer_compiles_for_v5e_at_gisette(one_chip, use_kernels):
    s, n = GISETTE["s"], GISETTE["n"]
    cfg = PCDNConfig(P=P, use_kernels=use_kernels)

    def outer(X, y, w, z, key, active):
        prob = L1Problem(design=DenseDesign(X=X), y=y, c=0.25)
        return LocalBackend(prob, cfg).outer(w, z, key, active,
                                             jnp.bool_(True),
                                             jnp.float32(0.25))

    text = _compiled_text(outer, one_chip, ((s, n), f32), ((s,), f32),
                          ((n,), f32), ((s,), f32), ((2,), jnp.uint32),
                          ((n,), jnp.bool_))
    assert ("tpu_custom_call" in text) == use_kernels
