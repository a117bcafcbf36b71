"""The program side of a toy second family, for the benchmark's own
tests: a token embedding, one residual SiLU mixing layer after an
RMSNorm, and an output head that is the embedding transposed (tied), so
that its parameter layout is not the dense decoder's. One chip only."""

import jax
import jax.numpy as jnp
import optax


def _logits(params, inputs, eps):
    emb = params["embed"]["embedding"]
    x = emb[inputs]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * params["norm"]["scale"]
    x = x + jax.nn.silu(h @ params["mix"]["kernel"])
    return x @ emb.T


def build(spec, key, mesh):
    if mesh is not None:
        raise ValueError("the toy family runs on one chip")
    m = spec["model"]
    v, h, o = m["vocab_size"], m["hidden_size"], m["optimizer"]
    params = {
        "embed": {"embedding": jnp.zeros((v, h))},
        "mix": {"kernel": jnp.zeros((h, h))},
        "norm": {"scale": jnp.ones((h,))},
    }
    tx = optax.adamw(o["learning_rate"], o["b1"], o["b2"], o["eps"],
                     weight_decay=o["weight_decay"])

    def loss(params, tokens):
        with jax.named_scope("forward"):
            logp = jax.nn.log_softmax(_logits(params, tokens[:, :-1], m["rms_norm_eps"]))
            return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()

    def train_step(state, tokens):
        value, grads = jax.value_and_grad(loss)(state["params"], tokens)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state["opt_state"], state["params"])
            params = optax.apply_updates(state["params"], updates)
        return {"params": params, "opt_state": opt_state}, {"loss": value}

    return train_step, {"params": params, "opt_state": tx.init(params)}
