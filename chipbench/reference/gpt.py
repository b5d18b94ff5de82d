"""GPT-3's language-model loss (Brown et al. 2020, section 2.1; the
architecture is GPT-2's, Radford et al. 2019): token + position
embeddings, pre-LN decoder blocks with causal attention and an exact
GELU, a final LayerNorm, logits through the tied embedding, and the
mean cross entropy of each position's next token. Departures, as the
configuration's `assumed` lists them: dense attention in every layer
(the paper alternates dense and locally banded sparse), LayerNorm
epsilon 1e-5, no dropout."""
import jax

from . import common as c


def loss_fn(w, batch, cfg, precision="float32"):
    ids, labels = batch
    w = c.stored(w, cfg["training"]["param_dtype"])
    eps = cfg["layer_norm_eps"]
    heads = cfg["num_heads"]
    b, s = ids.shape
    x = w["wte"][ids] + w["wpe"][:s]

    @jax.checkpoint
    def block(x, p):
        a = c.attention(precision, c.layer_norm(x, p["ln1_w"], p["ln1_b"],
                                                eps),
                        p["qkv_w"], p["qkv_b"], heads, causal=True)
        x = x + c.einsum(precision, "bsh,hk->bsk", a,
                         p["proj_w"]) + p["proj_b"]
        f = c.layer_norm(x, p["ln2_w"], p["ln2_b"], eps)
        f = c.einsum(precision, "bsh,hf->bsf", f, p["fc1_w"]) + p["fc1_b"]
        f = c.einsum(precision, "bsf,fh->bsh", c.gelu(f, tanh_form=False),
                     p["fc2_w"]) + p["fc2_b"]
        return x + f, None

    stacked = {k: v for k, v in w.items()
               if k not in ("wte", "wpe", "lnf_w", "lnf_b")}
    x, _ = jax.lax.scan(block, x, stacked)
    x = c.layer_norm(x, w["lnf_w"], w["lnf_b"], eps)
    total, count = c.summed_cross_entropy(
        precision, x[:, :-1].reshape(b * (s - 1), -1), w["wte"], None,
        labels[:, 1:].reshape(-1))
    return total / count
