"""Compile the main path for a described TPU v5e chip, at real widths.

Nothing runs: each program is lowered from shapes and compiled by the
TPU compiler for one chip of a ``v5e:2x2`` topology that is described,
not attached.  This catches what interpret mode cannot: blocks the
compiler refuses for the (8, 128) tiling, kernels that fall out of
``tpu_custom_call``, and step programs that do not fit 16 GB.

Kernels compile with ``interpret=False`` and the TPU-default ``alias``
accumulation strategies; the step programs are the full-width ViT-S
and MobileNetV3-L device round and server epoch at the paper's fleet
shape.  The topology is described inside a fixture, never at import
(see the test workers' shared TPU library lock).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10 ** 9          # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU compile written to the persistent cache cannot be read back
    # without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# qwen3-1.7b attention: hd 128, 16 q heads over 8 kv heads, S 4096, bf16
FA = dict(B=1, S=4096, Hkv=8, G=2, hd=128)


def _fa_shapes(one_chip):
    BKV, S, hd = FA["B"] * FA["Hkv"], FA["S"], FA["hd"]
    BH = BKV * FA["G"]
    bf16 = jnp.bfloat16
    q = jax.ShapeDtypeStruct((BH, S, hd), bf16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((BKV, S, hd), bf16, sharding=one_chip)
    do = jax.ShapeDtypeStruct((BH, S, hd), jnp.float32, sharding=one_chip)
    row = jax.ShapeDtypeStruct((BH, 1, S), jnp.float32, sharding=one_chip)
    return q, kv, do, row


FA_KW = dict(group=FA["G"], causal=True, window=0, softcap=0.0,
             scale=FA["hd"] ** -0.5, kv_len=FA["S"], block_q=128,
             block_k=128, interpret=False)


def test_flash_attention_fwd_compiles(one_chip):
    from repro.kernels.flash_attention import kernel as K

    q, kv, _, _ = _fa_shapes(one_chip)
    compiled = _compile(lambda q, k, v: K.flash_fwd(q, k, v, **FA_KW),
                        q, kv, kv)
    _assert_kernel(compiled)


def test_flash_attention_fused_bwd_alias_compiles(one_chip):
    from repro.kernels.flash_attention import kernel as K

    q, kv, do, row = _fa_shapes(one_chip)
    compiled = _compile(
        lambda q, k, v, do, lse, delta: K.flash_bwd_fused(
            q, k, v, do, lse, delta, dq_strategy="alias", **FA_KW),
        q, kv, kv, do, row, row)
    _assert_kernel(compiled)


# qwen3-1.7b loss: T 4096 tokens, D 2048, V 151936, bf16
XENT = dict(T=4096, D=2048, V=151936)


def _xent_shapes(one_chip):
    T, D, V = XENT["T"], XENT["D"], XENT["V"]
    h = jax.ShapeDtypeStruct((T, D), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((D, V), jnp.bfloat16, sharding=one_chip)
    lab = jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((T,), jnp.float32, sharding=one_chip)
    return h, w, lab, vec


def test_xent_fwd_compiles(one_chip):
    from repro.kernels.xent import kernel as XK

    h, w, lab, _ = _xent_shapes(one_chip)
    compiled = _compile(
        lambda h, w, lab: XK.xent_fwd(h, w, lab, interpret=False),
        h, w, lab)
    _assert_kernel(compiled)


def test_xent_bwd_alias_compiles(one_chip):
    from repro.kernels.xent import kernel as XK

    h, w, lab, vec = _xent_shapes(one_chip)
    compiled = _compile(
        lambda h, w, lab, lse, g: XK.xent_bwd(
            h, w, lab, lse, g, interpret=False, dh_strategy="alias"),
        h, w, lab, vec, vec)
    _assert_kernel(compiled)


def test_ssd_intra_compiles(one_chip):
    """mamba2-370m: chunk 256, head dim P 64, state N 128, 32 heads,
    a 4096-token sequence in 16 chunks."""
    from repro.kernels.ssd_chunk.kernel import ssd_intra_pallas

    B, nc, Q, H, P, N = 1, 16, 256, 32, 64, 128
    f32 = jnp.float32

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, f32, sharding=one_chip)

    compiled = _compile(
        lambda x, dt, ac, b, c: ssd_intra_pallas(x, dt, ac, b, c,
                                                 interpret=False),
        sds(B, nc, Q, H, P), sds(B, nc, Q, H), sds(B, nc, Q, H),
        sds(B, nc, Q, N), sds(B, nc, Q, N))
    _assert_kernel(compiled)


# ---------------------------------------------------------------------------
# full-width step programs of the paper's models at the paper's fleet shape
# ---------------------------------------------------------------------------

STEP_ARCHS = ("vit-s", "mobilenet-l")


def _step_setup(arch):
    """(model, run, device-phase state, server params, image pool) as
    shapes: 120 clients x device batch 32 samples."""
    from repro.configs import registry
    from repro.configs.base import RunConfig
    from repro.core import auxiliary, splitting
    from repro.models import build_model

    model = build_model(registry.get_config(arch))
    run = RunConfig(arch=arch)

    def init(key):
        dev, srv = splitting.split_params(model, model.init(key),
                                          run.split.split_point)
        aux = auxiliary.init_aux(model, key, run.split)
        return {"device": dev, "aux": aux}, srv

    dev_state, srv = jax.eval_shape(init, jax.random.PRNGKey(0))
    n = run.fed.num_clients * run.fed.device_batch_size
    img = model.cfg.img_size
    pool = {"images": jax.ShapeDtypeStruct((n, img, img, 3), jnp.float32),
            "labels": jax.ShapeDtypeStruct((n,), jnp.int32)}
    return model, run, dev_state, srv, pool


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("arch", STEP_ARCHS)
def test_device_round_compiles(one_chip, arch):
    from repro.core import steps

    model, run, dev_state, _, pool = _step_setup(arch)
    fed = run.fed
    K, H, b = fed.clients_per_round, fed.local_steps, fed.device_batch_size
    compiled = _compile(
        steps.make_device_round_pool_step(model, run),
        _on(one_chip, dev_state), _on(one_chip, pool),
        jax.ShapeDtypeStruct((K, H, b), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((K,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip))
    assert 0 < _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("arch", STEP_ARCHS)
def test_server_epoch_compiles(one_chip, arch):
    from repro.core import splitting, steps

    model, run, dev_state, srv, pool = _step_setup(arch)
    bs = run.fed.server_batch_size
    n = pool["labels"].shape[0]
    acts = jax.eval_shape(
        lambda d, x: splitting.device_forward(model, d, x,
                                              run.split.split_point),
        dev_state["device"], pool["images"])
    state = jax.eval_shape(
        lambda s: steps.init_server_state(model, run, s), srv)
    acts_pool = {"acts": jax.ShapeDtypeStruct(acts.shape, jnp.float32),
                 "labels": pool["labels"]}
    compiled = _compile(
        steps.make_server_epoch_fn(model, run),
        _on(one_chip, state), _on(one_chip, acts_pool),
        jax.ShapeDtypeStruct((n // bs, bs), jnp.int32, sharding=one_chip))
    assert 0 < _device_bytes(compiled) < HBM_BYTES
