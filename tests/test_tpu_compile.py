"""Compile the delta kernels for a described TPU v5e, at published widths.

Interpret-mode tests (tests/test_kernels.py and friends) cannot see what
the chip's compiler refuses: blocks off the (8, 128) tiling, casts Mosaic
does not lower, vector gathers, VMEM overruns.  These tests lower and
compile the kernels' jitted entry points for one chip of a described
``v5e:2x2`` topology — no chip needed — at deepseek-7b's projection shapes
(d_model 4096, d_ff 11008, the 102400-row unembed) and deepseek-moe-16b's
expert shapes (d_ff 1408 against d_model 2048), for an fp (bf16) and an
int8 base, plus one banked GEMM under a 4-device shard_map.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.quantize import QuantWeight
from repro.distributed import sharding as S
from repro.kernels import dispatch as D
from repro.kernels import ops as K

# (N, K) of the shadowed weights: deepseek-7b wq/wo, w_gate/w_up, w_down,
# the unembed; deepseek-moe-16b expert w_gate/w_up and w_down
SHAPES = [(4096, 4096), (11008, 4096), (4096, 11008), (102400, 4096),
          (1408, 2048), (2048, 1408)]
BANK = 3            # base slot + two variants


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    """The wrappers ask the (CPU) default backend whether to interpret;
    these compiles target the described chip, so force the Mosaic path.
    JAX's persistent cache cannot read back entries compiled for a chip
    that is not attached, so keep it out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(K, "_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _weight(n, k, base, sh):
    if base == "int8":
        return QuantWeight(q=_sds((n, k), jnp.int8, sh),
                           scale=_sds((n,), jnp.float16, sh))
    return _sds((n, k), jnp.bfloat16, sh)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


@pytest.mark.parametrize("base", ["fp", "int8"])
@pytest.mark.parametrize("n,k", SHAPES)
@pytest.mark.parametrize("m", [4, 128])
def test_banked_compiles(one_chip, m, n, k, base):
    args = (_sds((m, k), jnp.bfloat16, one_chip),
            _sds((m,), jnp.int32, one_chip),
            _sds((BANK, n, k // 8), jnp.uint8, one_chip),
            _sds((BANK, n), jnp.float16, one_chip),
            _sds((BANK, k), jnp.float16, one_chip),
            _weight(n, k, base, one_chip))
    _compile(K.bitlinear_axes_banked, *args)


@pytest.mark.parametrize("base", ["fp", "int8"])
@pytest.mark.parametrize("n,k", SHAPES)
def test_fused_compiles(one_chip, n, k, base):
    args = (_sds((128, k), jnp.bfloat16, one_chip),
            _sds((n, k // 8), jnp.uint8, one_chip),
            _sds((n,), jnp.float16, one_chip),
            _sds((k,), jnp.float16, one_chip),
            _weight(n, k, base, one_chip))
    _compile(K.bitlinear_axes, *args)


@pytest.mark.parametrize("base", ["fp", "int8"])
@pytest.mark.parametrize("n,k", SHAPES)
def test_unpack_apply_compiles(one_chip, n, k, base):
    w = _weight(n, k, base, one_chip)
    out_dtype = jnp.float16 if base == "int8" else jnp.bfloat16

    def rebuild(packed, v, w):
        return K.unpack_apply(packed, v, w, mode="row", out_dtype=out_dtype)

    _compile(rebuild, _sds((n, k // 8), jnp.uint8, one_chip),
             _sds((n,), jnp.float32, one_chip), w)


def test_sharded_expert_stack_compiles_on_four_devices(topo):
    """deepseek-moe-16b's expert stack (64 experts of (1408, 2048)) with
    its experts split over a (data=1, model=4) mesh: the per-expert sign
    deltas lower as ONE shard_map over the local experts."""
    from repro.models import moe as MOE
    from repro.models.delta_overlay import OverlayEntry
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    rules = S.rules_for("decode")
    e, m, f, d = 64, 8, 1408, 2048
    ex = NamedSharding(mesh, P("model"))
    args = (_sds((e, m, d), jnp.bfloat16, ex),
            QuantWeight(q=_sds((e, f, d), jnp.int8, ex),
                        scale=_sds((e, f), jnp.float16, ex)),
            OverlayEntry(packed=_sds((e, f, d // 8), jnp.uint8, ex),
                         v_row=_sds((e, f), jnp.float16, ex),
                         v_col=_sds((e, d), jnp.float16, ex)))
    before = D.memo_info()["declined"]
    with mesh, S.shard_ctx(mesh, rules):
        _compile(MOE._expert_mm, *args)
    assert D.memo_info()["declined"] == before


def test_sharded_banked_compiles_on_four_devices(topo):
    """One banked GEMM over a (data=1, model=4) mesh of the described
    devices: the delta lowers as a per-shard kernel under shard_map and the
    dispatcher declines nothing."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    rules = S.rules_for("decode")
    m, n, k = 8, 11008, 4096
    rep = NamedSharding(mesh, P())
    col = NamedSharding(mesh, P("model", None))
    args = (_sds((m, k), jnp.bfloat16, rep), _sds((m,), jnp.int32, rep),
            _sds((BANK, n, k // 8), jnp.uint8,
                 NamedSharding(mesh, P(None, "model", None))),
            _sds((BANK, n), jnp.float16, NamedSharding(mesh, P(None, "model"))),
            _sds((BANK, k), jnp.float16, rep),
            QuantWeight(q=_sds((n, k), jnp.int8, col),
                        scale=_sds((n,), jnp.float16,
                                   NamedSharding(mesh, P("model")))))

    def step(x, vidx, packed, vr, vc, w):
        return K.bitlinear_axes_banked(x, vidx, packed, vr, vc, w,
                                       waxes=("ffn", "embed"))

    before = D.memo_info()["declined"]
    with mesh, S.shard_ctx(mesh, rules):
        compiled = _compile(step, *args)
    assert D.memo_info()["declined"] == before
    assert compiled.as_text().count("tpu_custom_call") >= 1
