"""gluon — the high-level training API (reference:
``python/mxnet/gluon/__init__.py:?``)."""
from . import parameter
from .parameter import Parameter, Constant, ParameterDict
from . import block
from .block import Block, HybridBlock, SymbolBlock
from . import nn
from . import loss
from . import utils

_LAZY = {
    "trainer": ".trainer",
    "data": ".data",
    "rnn": ".rnn",
    "model_zoo": ".model_zoo",
    "contrib": ".contrib",
}


def __getattr__(name):
    if name == "Trainer":
        from .trainer import Trainer

        return Trainer
    if name == "FusedTrainStep":
        from .step_fusion import FusedTrainStep

        return FusedTrainStep
    if name == "step_fusion":
        # not ``from . import``: that asks this function first and recurses
        import importlib

        return importlib.import_module(".step_fusion", __name__)
    if name in _LAZY:
        import importlib

        mod = importlib.import_module(_LAZY[name], __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
