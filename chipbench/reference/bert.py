"""BERT's masked-LM forward in plain ``jax.numpy``, float32, no dropout.

The plain reference of the ``bert_*`` configurations: the same arithmetic as
``models/bert.py`` ``build_pretrain`` (post-LN encoder, exact GELU, masked
positions gathered before the tied vocabulary projection), written from the
paper and sharing no code with the program.  It takes the program's weights
by the names ``build_pretrain`` gives them and a feed in its layout.
"""

import math

import jax
import jax.numpy as jnp


def _layer_norm(x, scale, bias, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _attention(x, bias, w, prefix, heads):
    b, t, d = x.shape
    if prefix + ".qkv.w" in w:
        q, k, v = jnp.split(x @ w[prefix + ".qkv.w"] + w[prefix + ".qkv.b"],
                            3, axis=-1)
    else:
        q, k, v = (x @ w[prefix + ".%s.w" % n] + w[prefix + ".%s.b" % n]
                   for n in "qkv")

    def split(a):
        return a.reshape(b, t, heads, d // heads).transpose(0, 2, 1, 3)

    scores = split(q) @ split(k).transpose(0, 1, 3, 2) \
        / math.sqrt(d // heads) + bias
    ctx = jax.nn.softmax(scores, axis=-1) @ split(v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, d)
    return ctx @ w[prefix + ".o.w"] + w[prefix + ".o.b"]


def forward(w, feed, config):
    """``{"logits": [B * max_pred, vocab], "loss": scalar}``."""
    ids = feed["input_ids"]
    x = (w["bert.word_emb"][ids] + w["bert.pos_emb"][feed["pos_ids"]]
         + w["bert.type_emb"][feed["token_type_ids"]])
    x = _layer_norm(x, w["bert.emb_ln.scale"], w["bert.emb_ln.bias"])
    for i in range(config["num_hidden_layers"]):
        p = "bert.layer%d" % i
        attn = _attention(x, feed["attn_mask_bias"], w, p + ".attn",
                          config["num_attention_heads"])
        x = _layer_norm(x + attn, w[p + ".ln1.scale"], w[p + ".ln1.bias"])
        ff = jax.nn.gelu(x @ w[p + ".ffn1.w"] + w[p + ".ffn1.b"],
                         approximate=False)
        ff = ff @ w[p + ".ffn2.w"] + w[p + ".ffn2.b"]
        x = _layer_norm(x + ff, w[p + ".ln2.scale"], w[p + ".ln2.bias"])
    picked = jnp.take_along_axis(x, feed["mask_pos"][:, :, None], axis=1)
    logits = picked.reshape(-1, x.shape[-1]) @ w["bert.word_emb"].T
    labels = feed["mlm_labels"].reshape(-1)
    weights = feed["mlm_weights"].reshape(-1)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               labels[:, None], axis=1)[:, 0]
    return {"logits": logits, "loss": (nll * weights).sum() / weights.sum()}
