"""Bytes one decode tick of a GLM-MoE-DSA model needs, from its shapes: every
weight outside the routed experts once (the head whole, the embedding rows of
the active slots), the TOUCHED held experts' weights once, a layer's index keys
up to each active slot's position (``kv_visible`` positions of ``index_head_dim``
values), the selected latent rows (``kv_selected`` of ``kv_lora_rank +
qk_rope_head_dim`` values: the logical row, not its stored padding) and the new
token's two rows written.  ``kv_visible`` / ``kv_selected`` are the tick records'
fields, a layer; a program that reads a slot's whole table, or every held
expert whatever the routing, reads low."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_flops_glm_prefill",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "glm_moe_dsa_prefill.py"))
_prefill = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_prefill)


def expert_bytes(cfg, itemsize=2):
    return _prefill.linear_params(cfg)[2] * itemsize


def layer_params(cfg):
    """-> (a dense layer's, an expert layer's held) parameters, norms counted:
    PERF.md's parameter table."""
    mla, idx, expert, router, dense = _prefill.linear_params(cfg)
    norms = 2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"] \
        + 2 * cfg["index_head_dim"]
    held = cfg["experts_held"][1]
    return (mla + idx + dense + norms,
            mla + idx + cfg["n_shared_experts"] * expert + router
            + cfg["router_experts"] + held * expert + norms)


def weight_bytes(cfg, itemsize=2):
    """All of the model as served here."""
    dense, moe = layer_params(cfg)
    n_dense = cfg["first_k_dense_replace"]
    top = 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    return (n_dense * dense + (cfg["num_hidden_layers"] - n_dense) * moe
            + top) * itemsize


def fixed_weight_bytes(cfg, itemsize=2):
    """Every weight a tick reads whatever the routing and the slots: all but
    the routed experts' and the embedding."""
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return weight_bytes(cfg, itemsize) \
        - n_moe * cfg["experts_held"][1] * expert_bytes(cfg, itemsize) \
        - cfg["vocab_size"] * cfg["hidden_size"] * itemsize


def cache_bytes_per_token(cfg, itemsize=2):
    """The logical rows a token leaves over the layers: latent and index key."""
    return cfg["num_hidden_layers"] * itemsize * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"] + cfg["index_head_dim"])


def bytes_needed(cfg, active_slots, kv_visible, kv_selected, experts_touched,
                 itemsize=2):
    layers = cfg["num_hidden_layers"]
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return fixed_weight_bytes(cfg, itemsize) \
        + experts_touched * expert_bytes(cfg, itemsize) \
        + active_slots * cfg["hidden_size"] * itemsize \
        + layers * itemsize * (kv_visible * cfg["index_head_dim"]
                               + kv_selected * latent) \
        + active_slots * cache_bytes_per_token(cfg, itemsize)
