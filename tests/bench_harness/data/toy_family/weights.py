"""Weights from the seed: the contract of ``benchmark/weights.py``
(``build_params(cfg, seed, each=None)``) for a family whose experts are
``moe_intermediate_size`` wide and whose queries have a norm of their own."""
import jax
import jax.numpy as jnp


def build_params(cfg, seed, each=None):
    h, e = cfg["hidden_size"], cfg["n_routed_experts"]
    shapes = {"wq": (h, h), "q_norm": (cfg["head_dim"],), "router": (h, e),
              "w_gate": (e, h, cfg["moe_intermediate_size"])}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    drawn = {n: jax.random.normal(k, shapes[n], jnp.float32) for n, k in zip(shapes, keys)}
    return {n: t if each is None else each(n, t) for n, t in drawn.items()}
