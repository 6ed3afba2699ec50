"""Bytes one decode tick of Keye-VL-2.0's language model needs, from its shapes:
every weight outside the experts once (the head whole, the embedding rows of the
active slots), the TOUCHED experts' weights once, a layer's index keys up to
each active slot's position (``kv_visible`` positions of ``indexer_head_dim``
values: the logical key, not its stored padding), the selected K and V rows
(``kv_selected`` positions of ``2 x num_key_value_heads x head_dim`` values) and
the new token's three rows written.  ``kv_visible`` / ``kv_selected`` are the
tick records' fields, a layer; a program that scores a slot's whole table, reads
a context's every K/V row, or every expert whatever the routing, reads low."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_flops_keye_prefill",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "keye_vl2_prefill.py"))
_prefill = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_prefill)


def expert_bytes(cfg, itemsize=2):
    return _prefill.linear_params(cfg)[2] * itemsize


def layer_params(cfg):
    """-> (a layer's parameters outside its experts, a layer's in all), norms
    and the index key norm's bias counted: PERF.md's parameter table."""
    attn, idx, expert, router = _prefill.linear_params(cfg)
    norms = 2 * cfg["hidden_size"] + 2 * cfg["head_dim"] \
        + 2 * cfg["sa_config"]["indexer_head_dim"]
    outside = attn + idx + router + norms
    return outside, outside + cfg["num_experts"] * expert


def weight_bytes(cfg, itemsize=2):
    """All of the model as served here."""
    top = 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)[1] + top) * itemsize


def fixed_weight_bytes(cfg, itemsize=2):
    """Every weight a tick reads whatever the routing and the slots: all but
    the experts' and the embedding."""
    return (cfg["num_hidden_layers"] * layer_params(cfg)[0]
            + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]) \
        * itemsize


def kv_row_values(cfg):
    """Values of K and V a position keeps a layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def cache_bytes_per_token(cfg, itemsize=2):
    """The logical rows a token leaves over the layers: K, V and index key."""
    return cfg["num_hidden_layers"] * itemsize * (
        kv_row_values(cfg) + cfg["sa_config"]["indexer_head_dim"])


def selection_bytes(cfg, kv_visible, kv_selected, itemsize=2):
    """What a tick's scoring and selected attention read over the layers."""
    return cfg["num_hidden_layers"] * itemsize * (
        kv_visible * cfg["sa_config"]["indexer_head_dim"]
        + kv_selected * kv_row_values(cfg))


def bytes_needed(cfg, active_slots, kv_visible, kv_selected, experts_touched,
                 itemsize=2):
    return fixed_weight_bytes(cfg, itemsize) \
        + experts_touched * expert_bytes(cfg, itemsize) \
        + active_slots * cfg["hidden_size"] * itemsize \
        + selection_bytes(cfg, kv_visible, kv_selected, itemsize) \
        + active_slots * cache_bytes_per_token(cfg, itemsize)
