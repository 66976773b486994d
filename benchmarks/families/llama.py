"""The dense GQA + SwiGLU decoder family, as ``dlrover_tpu.models.llama``
computes it: pre-norm RMSNorm, rotary on q and k, grouped-query causal
attention, SwiGLU, untied head, mean next-token cross-entropy.

``build`` maps a configuration file (the model's published keys) to
what a job needs from the program: its config, parameter specs, init
and loss. ``reference_loss`` is the benchmark's own plain forward and
loss, which shares no code with the program.
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp

from benchmarks.harness import flops

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _sizes(config: dict) -> dict:
    dim = config["hidden_size"]
    n_heads = config["num_attention_heads"]
    head_dim = config.get("head_dim", dim // n_heads)
    if head_dim * n_heads != dim:
        raise ValueError(
            "the program's LlamaConfig derives head_dim = dim / n_heads; "
            f"{config['name']} has head_dim {head_dim} x {n_heads} != {dim}"
        )
    for key, want in (("sliding_window", None), ("tie_word_embeddings", False),
                      ("hidden_act", "silu")):
        if config.get(key, want) != want:
            raise ValueError(
                f"{config['name']}: {key}={config[key]!r} is not what "
                f"models/llama.py computes ({want!r})"
            )
    return dict(
        n_layers=config["num_hidden_layers"], dim=dim, n_heads=n_heads,
        n_kv_heads=config["num_key_value_heads"], head_dim=head_dim,
        ffn_dim=config["intermediate_size"], vocab_size=config["vocab_size"],
    )


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel import named_shardings

    sizes = _sizes(config)
    assumed = config["assumed"]
    cfg = llama.LlamaConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["dim"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        n_kv_heads=sizes["n_kv_heads"], ffn_dim=sizes["ffn_dim"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
        remat_policy="all" if assumed["remat"] == "off" else assumed["remat"],
    )
    specs = llama.param_specs(cfg)
    std = float(config["initializer_range"])
    if std != 0.02:
        raise ValueError("models/llama.py initialises with sigma 0.02 only")
    init = jax.jit(
        lambda key: llama.init_params(cfg, key),
        out_shardings=named_shardings(mesh, specs),
    )
    return types.SimpleNamespace(
        cfg=cfg,
        param_specs=specs,
        init_params=init,
        # the mesh goes in as examples/llama_pretrain.py passes it: the
        # path PR 22 ran on the chip
        loss_fn=lambda p, t: llama.loss_fn(p, t, cfg, mesh),
        param_count=llama.param_count(cfg),
        flops_per_token=lambda seq: flops.dense_decoder_flops_per_token(
            seq=seq, **sizes),
        # random weights at sigma give logits of variance dim x sigma^2,
        # so the first loss is ln V + dim x sigma^2 / 2 (PERF.md, PR 22)
        expected_first_loss=math.log(sizes["vocab_size"])
        + sizes["dim"] * std * std / 2.0,
        reference_loss=lambda params, tokens: reference_loss(
            params, tokens, config),
    )


# ---------------------------------------------------------------------------
# The plain reference: float32, matmul precision "highest", no kernel, no
# chunking. One departure from nothing: the rotary pairing is the
# published one of the Hugging Face implementation (first half of a head
# against its second half), which is also the program's.
# ---------------------------------------------------------------------------

def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _rotary(x, theta):
    # x: (b, s, heads, head_dim)
    s, hd = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ref_layer(x, lp, n_heads, n_kv_heads, head_dim, theta, eps):
    b, s, _ = x.shape
    y = _rms_norm(x, lp["attn_norm"], eps)
    q = _rotary((y @ lp["wq"]).reshape(b, s, n_heads, head_dim), theta)
    k = _rotary((y @ lp["wk"]).reshape(b, s, n_kv_heads, head_dim), theta)
    v = (y @ lp["wv"]).reshape(b, s, n_kv_heads, head_dim)
    group = n_heads // n_kv_heads
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(b, s, n_heads * head_dim) @ lp["wo"]
    y = _rms_norm(x, lp["mlp_norm"], eps)
    return x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) @ lp["w_down"]


def _ref_head_loss(x, final_norm, lm_head, tokens, eps):
    logits = _rms_norm(x, final_norm, eps) @ lm_head
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)


def reference_loss(params, tokens, config: dict) -> float:
    """Mean next-token cross-entropy of ``tokens`` (b, s) under
    ``params`` (the program's parameter tree, any dtype), one layer cast
    to float32 at a time so that it fits beside a full device."""
    sizes = _sizes(config)
    eps = float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda table, t: f32(table)[t])
        layer = jax.jit(
            lambda x, lp: _ref_layer(
                x, f32(lp), sizes["n_heads"], sizes["n_kv_heads"],
                sizes["head_dim"], theta, eps)
        )
        head = jax.jit(
            lambda x, fn, w, t: _ref_head_loss(x, f32(fn), f32(w), t, eps)
        )
        x = embed(params["embed"], tokens)
        for i in range(sizes["n_layers"]):
            x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
        return float(head(x, params["final_norm"], params["lm_head"], tokens))
