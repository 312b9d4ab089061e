"""Counts: what the readers ask of a model (``ctx["model_costs"]``), for
experts under ``moe_intermediate_size`` and two attention kernels a layer
(a selector and the attention proper).  No JAX."""


def weight_bytes(cfg, dtype_bytes=2):
    h = cfg["hidden_size"]
    attn = 2 * h * cfg["head_dim"] * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
    experts = cfg["n_routed_experts"] * (3 * h * cfg["moe_intermediate_size"] + h)
    return float(dtype_bytes) * (
        cfg["num_hidden_layers"] * (attn + experts) + h * cfg["vocab_size"])


def kv_bytes_per_token(cfg, dtype_bytes=2):
    return 2.0 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * cfg["head_dim"] * dtype_bytes


def forward_passes(op_counts, cfg):
    kernels = sum(n for label, n in op_counts.items() if "attention" in label)
    return kernels / (2 * cfg["num_hidden_layers"])
