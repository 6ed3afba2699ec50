"""Model families beyond the vision zoo (BASELINE configs 3 and 5).

``transformer``/``bert`` mirror GluonNLP's model surface; ``llama`` is the
stretch config (modern LLM under mx.tpu() — NEW capability vs the
reference).
"""
from . import transformer
from .transformer import Transformer
from . import bert
from .bert import BERTModel, BERTClassifier, bert_base, bert_large, \
    bert_tiny


def __getattr__(name):
    if name in ("llama", "fm", "moe", "lfm2", "sdar", "glm_moe_dsa",
                "qwen3_next", "ouro", "nemotron_h", "keye_vl2"):
        import importlib

        mod = importlib.import_module("." + name, __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
