"""§Perf optimization knobs preserve semantics (EXPERIMENTS.md §Perf)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_arch
from repro.models import attention as A
from repro.models import build


@pytest.mark.parametrize("window", [0, 5, 16])
def test_chunked_attention_matches_dense(window):
    cfg = dataclasses.replace(
        get_arch("qwen3-1.7b").reduced(), chunked_attn=True, attn_chunk=8
    )
    p = A.init_attn(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    out_c = A.attention(p, x, cfg, causal=True, window=window)
    cfg0 = dataclasses.replace(cfg, chunked_attn=False)
    out_d = A.attention(p, x, cfg0, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out_c), np.asarray(out_d), rtol=2e-5, atol=2e-5
    )


def test_chunked_attention_hybrid_dynwin():
    cfg = dataclasses.replace(
        get_arch("hymba-1.5b").reduced(), chunked_attn=True, attn_chunk=8
    )
    m = build(cfg)
    p = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, cfg.vocab_size)
    l1, _ = m.forward(p, {"tokens": toks})
    l0, _ = build(dataclasses.replace(cfg, chunked_attn=False)).forward(
        p, {"tokens": toks}
    )
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=2e-3, atol=2e-3)


def test_vocab_padding_transparent():
    cfg = dataclasses.replace(get_arch("qwen3-1.7b").reduced(), vocab_pad_to=64)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    assert params["embed"].shape[0] % 64 == 0
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab_size)
    logits, _ = m.forward(params, {"tokens": toks})
    assert logits.shape[-1] == cfg.vocab_size
    lg, state = m.decode_step(
        params, toks[:, :1], m.init_state(params, {"tokens": toks}, max_len=8)
    )
    assert lg.shape[-1] == cfg.vocab_size


def test_kv_fsdp_spec():
    from jax.sharding import AbstractMesh

    from repro.launch.shardings import param_spec

    mesh = AbstractMesh((16, 16), ("data", "model"))
    cfg = get_arch("granite-20b")  # kv=1 — can't head-shard
    leaf = jax.ShapeDtypeStruct((52, 6144, 1, 128), jnp.bfloat16)
    base = param_spec("layers/attn/wk", leaf, cfg, mesh)
    opt = param_spec("layers/attn/wk", leaf, cfg, mesh, kv_fsdp=True)
    assert base[1] == "model"  # row-parallel baseline
    assert opt[1] == "data"  # FSDP-style weight sharding
