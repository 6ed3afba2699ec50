"""Graph export/import: the serving path.

Reference surfaces (SURVEY §3.5): ``HybridBlock.export`` writes
``prefix-symbol.json`` + ``prefix-0000.params``; ``SymbolBlock.imports``
(and the C ``MXPredCreate`` predict API) loads them back and runs
inference.

TPU-native redesign — two formats, one importer:

  * **Export** realises the north star's "CachedOp → StableHLO": the
    hybridized block's pure function is serialized with ``jax.export``
    (portable StableHLO artifact, ``prefix-0000.stablehlo``) next to a
    ``prefix-symbol.json`` metadata header and an MXNet-binary
    ``prefix-0000.params``.  A SymbolBlock restored from it runs the
    compiled graph without any python model code.
  * **Import of reference nnvm JSON**: ``SymbolBlock.imports`` detects the
    reference's symbol-json ("nodes"/"arg_nodes"/"heads") and executes it
    directly against this framework's op registry (op names and attribute
    spellings match the reference's registry) — models exported by actual
    MXNet run here unchanged, covering the ``MXPredCreate`` use-case.
"""
from __future__ import annotations

import ast
import json
import os

import numpy as np

from ..base import MXNetError
from ..ndarray import NDArray
from .. import autograd as ag

_FORMAT_KEY = "mxnet_tpu_format"


# ---------------------------------------------------------------------------
# Export
# ---------------------------------------------------------------------------

def export_block(block, path, epoch=0):
    """Serialize a hybridized block (must have run forward at least once so
    a cached graph exists — same precondition as the reference's export)."""
    from .block import CachedOp, _CachedGraph

    cached = getattr(block, "_cached_op", None)
    if cached is None or not cached._graphs:
        raise MXNetError(
            "export requires hybridize() and at least one forward call "
            "(the reference has the same requirement)")
    sig, graph = next(iter(cached._graphs.items()))
    import jax
    import jax.export  # a submodule: `import jax` alone does not load it

    params = graph.params
    p_raws = tuple(p.data()._data for p in params)
    in_shapes = sig[0]
    in_raws = tuple(jax.numpy.zeros(s, np.dtype(dt))
                    for s, dt in in_shapes)
    key = jax.random.PRNGKey(0)

    def infer_fn(p, x, k):
        outs, _aux, _stats = graph._pure(list(p), list(x), k)
        return outs

    exported = jax.export.export(jax.jit(infer_fn))(p_raws, in_raws, key)
    hlo_path = f"{path}-{epoch:04d}.stablehlo"
    with open(hlo_path, "wb") as f:
        f.write(exported.serialize())

    from .. import serialization

    payload = {}
    for p in params:
        prefix = "aux:" if p.grad_req == "null" else "arg:"
        payload[prefix + p.name] = p.data()
    serialization.save_ndarrays(f"{path}-{epoch:04d}.params", payload)

    meta = {
        _FORMAT_KEY: "stablehlo",
        "version": 1,
        "param_names": [p.name for p in params],
        "param_kinds": ["aux" if p.grad_req == "null" else "arg"
                        for p in params],
        "input_shapes": [list(s) for s, _ in in_shapes],
        "input_dtypes": [dt for _, dt in in_shapes],
        "num_outputs": graph.struct.num_leaves if graph.struct else 1,
        "stablehlo_file": os.path.basename(hlo_path),
    }
    with open(f"{path}-symbol.json", "w") as f:
        json.dump(meta, f, indent=2)
    return f"{path}-symbol.json", f"{path}-{epoch:04d}.params"


# ---------------------------------------------------------------------------
# Import
# ---------------------------------------------------------------------------

def load_symbol_json(symbol_file):
    with open(symbol_file) as f:
        return json.load(f)


def import_block(symbol_file, input_names, param_file=None, ctx=None):
    meta = load_symbol_json(symbol_file)
    if isinstance(input_names, str):
        input_names = [input_names]
    if meta.get(_FORMAT_KEY) == "stablehlo":
        return _import_stablehlo(symbol_file, meta, param_file)
    if "nodes" in meta:
        return _import_nnvm(meta, input_names, param_file)
    raise MXNetError(f"unrecognised symbol file format in {symbol_file!r}")


def _import_stablehlo(symbol_file, meta, param_file):
    import jax
    import jax.export  # a submodule: `import jax` alone does not load it

    from .block import HybridBlock, SymbolBlock
    from .. import serialization

    hlo_path = os.path.join(os.path.dirname(os.path.abspath(symbol_file)),
                            meta["stablehlo_file"])
    with open(hlo_path, "rb") as f:
        exported = jax.export.deserialize(bytearray(f.read()))
    if param_file is None:
        raise MXNetError("param_file is required for stablehlo imports")
    loaded = serialization.load_ndarrays(param_file)
    loaded = {k.removeprefix("arg:").removeprefix("aux:"): v
              for k, v in loaded.items()}
    p_raws = []
    for name in meta["param_names"]:
        if name not in loaded:
            raise MXNetError(f"parameter {name!r} missing in {param_file!r}")
        p_raws.append(loaded[name]._data)
    p_raws = tuple(p_raws)

    block = SymbolBlock(prefix="symbolblock_")
    key = None

    def fn(F, args, params):
        import jax as _jax

        raws = tuple(a._data for a in args)
        outs = exported.call(p_raws, raws, _jax.random.PRNGKey(0))
        nd_outs = [NDArray(o) for o in outs]
        return nd_outs[0] if len(nd_outs) == 1 else tuple(nd_outs)

    block._fn = fn
    block._sb_meta = meta
    return block


# --- nnvm-json execution ----------------------------------------------------

def _parse_attr(value):
    """MXNet serializes op attrs as strings ("(3, 3)", "64", "True")."""
    if not isinstance(value, str):
        return value
    try:
        return ast.literal_eval(value)
    except (ValueError, SyntaxError):
        return value


# legacy / symbol-only op names → registry names (reference aliases that the
# op registry does not carry natively)
_OP_RENAMES = {
    "SoftmaxOutput": "softmax",
    "LinearRegressionOutput": "identity",
    "LogisticRegressionOutput": "sigmoid",
    "MAERegressionOutput": "identity",
    "_copy": "identity",
    "_Plus": "elemwise_add",
    "_plus": "elemwise_add",
    "_mul": "elemwise_mul",
    "_sub": "elemwise_sub",
    "_div": "elemwise_div",
    "Cast": "cast",
    "SliceChannel": "split",
    "Crop": "slice_like",
}

# ops whose trailing label input is dropped at inference
_DROP_LABEL_OPS = {"SoftmaxOutput", "LinearRegressionOutput",
                   "LogisticRegressionOutput", "MAERegressionOutput"}


class _NNVMGraphRunner:
    """Topological executor over a reference symbol-json graph using this
    framework's op registry (reference: GraphExecutor::RunOps,
    src/executor/graph_executor.cc:? — here per-op dispatch that XLA then
    fuses under the SymbolBlock's own hybridize)."""

    def __init__(self, graph, input_names):
        self.nodes = graph["nodes"]
        self.heads = [tuple(h[:2]) for h in graph["heads"]]
        self.arg_nodes = set(graph["arg_nodes"])
        self.input_names = list(input_names)
        self.param_names = [
            n["name"] for i, n in enumerate(self.nodes)
            if i in self.arg_nodes and n["name"] not in self.input_names]

    def _used_nodes(self):
        """Nodes reachable from the heads after inference-time label
        dropping (unused label args need no binding)."""
        used = set()
        stack = [nid for nid, _ in self.heads]
        while stack:
            nid = stack.pop()
            if nid in used:
                continue
            used.add(nid)
            node = self.nodes[nid]
            entries = node["inputs"]
            if node["op"] in _DROP_LABEL_OPS and len(entries) > 1:
                entries = entries[:1]
            stack.extend(e[0] for e in entries)
        return used

    def run(self, inputs, params):
        from ..ops import registry as op_registry

        used = self._used_nodes()
        values = {}  # nid -> tuple of outputs
        for nid, node in enumerate(self.nodes):
            if nid not in used:
                continue
            op_name = node["op"]
            name = node["name"]
            if op_name == "null":
                if name in inputs:
                    values[nid] = (inputs[name],)
                elif name in params:
                    values[nid] = (params[name],)
                else:
                    raise MXNetError(
                        f"unbound input {name!r} (inputs: "
                        f"{sorted(inputs)}; params not loaded?)")
                continue
            attrs = {k: _parse_attr(v) for k, v in
                     (node.get("attrs") or node.get("param") or {}).items()}
            entries = node["inputs"]
            if op_name in _DROP_LABEL_OPS and len(entries) > 1:
                entries = entries[:1]
            args = [values[e[0]][e[1]] for e in entries]
            # output/loss heads run their inference-mode rename (label was
            # dropped above), never the training op from the registry
            if op_name in _DROP_LABEL_OPS:
                fn = op_registry.get_op(_OP_RENAMES[op_name])
                if op_name == "SoftmaxOutput":
                    # multi_output softmaxes the class axis 1 (reference
                    # src/operator/softmax_output.cc:? enum), not the last
                    attrs = {"axis": 1 if attrs.get("multi_output") else -1}
            else:
                fn = op_registry.get_op(op_name) or \
                    op_registry.get_op(_OP_RENAMES.get(op_name, ""))
            if fn is None:
                raise MXNetError(
                    f"op {op_name!r} (node {name!r}) is not implemented in "
                    "the op registry")
            out = fn(*args, **attrs)
            values[nid] = out if isinstance(out, tuple) else (out,)
        outs = [values[nid][oidx] for nid, oidx in self.heads]
        return outs[0] if len(outs) == 1 else tuple(outs)


def _import_nnvm(graph, input_names, param_file):
    from .block import SymbolBlock

    runner = _NNVMGraphRunner(graph, input_names)
    params = {}
    if param_file:
        from .. import serialization

        loaded = serialization.load_ndarrays(param_file)
        params = {k.removeprefix("arg:").removeprefix("aux:"): v
                  for k, v in loaded.items()}
    block = SymbolBlock(prefix="symbolblock_")

    def fn(F, args, _params):
        inputs = dict(zip(runner.input_names, args))
        with ag.predict_mode():
            return runner.run(inputs, params)

    block._fn = fn
    block._nnvm_runner = runner
    block._nnvm_params = params
    return block
