"""Mosaic compiles of the paged Pallas kernels for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a chip that is described, not attached, at qwen3-1.7b widths (16 query
heads, 8 KV heads, head_dim 128, 16-token blocks, 16 lanes x 2,048
tokens), and the HLO must hold the Mosaic custom call.  This catches
what interpret mode cannot: block shapes off the (8, 128) tiling, and
kernels the compiler refuses.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import pytest

B, H, K, D, BS, T = 16, 16, 8, 128, 16, 128
N_BLOCKS = 1 + B * T


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back from the persistent
    # cache; keep it out of the cache while these tests run
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _decode(q, kp, vp, bt, pos, own):
    from repro.kernels.paged_decode_attention import paged_decode_attention
    return paged_decode_attention(q, kp, vp, bt, pos)


def _decode_lse_combined(q, kp, vp, bt, pos, own):
    """Two pool stripes through the LSE kernel, merged by combine_lse."""
    import jax.numpy as jnp
    from repro.kernels.paged_decode_attention import (
        combine_lse, paged_decode_attention_lse)
    half = kp.shape[0] // 2
    outs = [paged_decode_attention_lse(q, kp[r * half:(r + 1) * half],
                                       vp[r * half:(r + 1) * half],
                                       bt, pos, own)
            for r in range(2)]
    return combine_lse(jnp.stack([o for o, _ in outs]),
                       jnp.stack([lse for _, lse in outs]))


def _prefill(q, kp, vp, bt, pos, own):
    from repro.kernels.paged_decode_attention import paged_prefill_attention
    return paged_prefill_attention(q, kp, vp, bt, pos)


def _verify(q, kp, vp, bt, pos, own):
    from repro.kernels.paged_decode_attention import paged_verify_attention
    return paged_verify_attention(q, kp, vp, bt, pos)


@pytest.mark.parametrize("kernel, q_rows", [
    (_decode, None),
    (_decode_lse_combined, None),
    (_prefill, 16),       # a 16-token prefill chunk
    (_verify, 5),         # the input token plus K=4 drafts
], ids=["paged_decode", "paged_decode_lse+combine", "paged_prefill",
        "paged_verify"])
def test_kernel_compiles_to_mosaic(one_chip, kernel, q_rows):
    import jax
    import jax.numpy as jnp
    q_shape = (B, H, D) if q_rows is None else (B, q_rows, H, D)
    args = (_sds(q_shape, jnp.bfloat16, one_chip),
            _sds((N_BLOCKS + 1, BS, K, D), jnp.bfloat16, one_chip),
            _sds((N_BLOCKS + 1, BS, K, D), jnp.bfloat16, one_chip),
            _sds((B, T), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip),
            _sds((B, T), jnp.int32, one_chip))
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
