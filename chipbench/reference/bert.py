"""BERT pre-training loss (Devlin et al. 2018, section 3.1 and appendix
A.2): token + position + segment embeddings, post-LN encoder layers,
the MLM head (dense, GELU, LayerNorm, decoder tied to the word
embeddings plus a bias) over the masked positions, the NSP head on the
pooled first token; the loss is the sum of the two mean cross
entropies. Departures from the paper, as the configuration's `assumed`
lists them: tanh GELU, LayerNorm epsilon 1e-5, no dropout."""
import jax
import jax.numpy as jnp

from . import common as c


def loss_fn(w, batch, cfg, precision="float32"):
    ids, tok, labels, nsp_labels = batch
    w = c.stored(w, cfg["training"]["param_dtype"])
    eps = cfg["layer_norm_eps"]
    tanh = cfg["hidden_act"] == "gelu_tanh"
    heads = cfg["num_attention_heads"]
    b, s = ids.shape
    x = w["word_emb"][ids] + w["pos_emb"][:s] + w["type_emb"][tok]
    x = c.layer_norm(x, w["emb_ln_w"], w["emb_ln_b"], eps)

    @jax.checkpoint
    def layer(x, p):
        a = c.attention(precision, x, p["qkv_w"], p["qkv_b"], heads,
                        causal=False)
        a = c.einsum(precision, "bsh,hk->bsk", a, p["out_w"]) + p["out_b"]
        x = c.layer_norm(x + a, p["ln1_w"], p["ln1_b"], eps)
        f = c.einsum(precision, "bsh,hf->bsf", x, p["fc1_w"]) + p["fc1_b"]
        f = c.einsum(precision, "bsf,fh->bsh", c.gelu(f, tanh),
                     p["fc2_w"]) + p["fc2_b"]
        return c.layer_norm(x + f, p["ln2_w"], p["ln2_b"], eps), None

    stacked = {k: v for k, v in w.items()
               if k in ("qkv_w", "qkv_b", "out_w", "out_b", "ln1_w",
                        "ln1_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b",
                        "ln2_w", "ln2_b")}
    x, _ = jax.lax.scan(layer, x, stacked)

    t = c.einsum(precision, "th,hk->tk", x.reshape(b * s, -1),
                 w["transform_w"]) + w["transform_b"]
    t = c.layer_norm(c.gelu(t, tanh), w["head_ln_w"], w["head_ln_b"], eps)
    total, count = c.summed_cross_entropy(
        precision, t, w["word_emb"], w["decoder_b"], labels.reshape(-1),
        ignore=-100)
    mlm = total / count

    pooled = jnp.tanh(c.einsum(precision, "bh,hk->bk", x[:, 0],
                               w["pooler_w"]) + w["pooler_b"])
    nsp_logits = c.einsum(precision, "bh,hk->bk", pooled,
                          w["nsp_w"]) + w["nsp_b"]
    nsp = -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(nsp_logits, axis=-1), nsp_labels[:, None],
        axis=-1))
    return mlm + nsp
