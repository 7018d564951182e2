"""ComputationGraph configuration: DAG of named vertices.

TPU-native equivalent of the reference's
``nn/conf/ComputationGraphConfiguration.java`` (664 LoC) and its
``GraphBuilder`` (``addLayer:525``, ``addInputs:561``, ``setOutputs:589``,
``addVertex:605``, ``build:614``), plus the vertex configs in
``nn/conf/graph/`` (MergeVertex, ElementWiseVertex, SubsetVertex,
StackVertex, UnstackVertex, ScaleVertex, PreprocessorVertex, L2Vertex,
L2NormalizeVertex) and ``nn/conf/graph/rnn/`` (LastTimeStepVertex,
DuplicateToTimeSeriesVertex).

The reference materializes vertex objects and runs Kahn's algorithm at
runtime (``ComputationGraph.topologicalSortOrder():850``).  Here the topo
sort happens once at config build; execution is pure function composition
traced by jax, so the whole graph compiles to a single XLA program.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import inputs as _inputs
from . import serde
from ..layers.base import BaseLayerConfig

InputType = _inputs.InputType
Array = jax.Array


# --------------------------------------------------------------- vertices
@dataclasses.dataclass
class BaseVertex:
    """A DAG node: consumes the activations of ``inputs`` (vertex names),
    produces one activation.  Stateless vertices implement ``apply``;
    LayerVertex delegates to its layer config."""

    inputs: List[str] = dataclasses.field(default_factory=list)

    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *xs: Array, masks=None) -> Array:
        raise NotImplementedError


@serde.register("vertex_layer")
@dataclasses.dataclass
class LayerVertex(BaseVertex):
    """Wraps a layer config (reference ``nn/conf/graph/LayerVertex.java``);
    optional input preprocessor applied before the layer."""

    layer: Optional[BaseLayerConfig] = None
    preprocessor: Optional[object] = None

    def output_type(self, *input_types: InputType) -> InputType:
        it = input_types[0]
        if self.preprocessor is not None:
            it = self.preprocessor.output_type(it)
        return self.layer.output_type(it)


@serde.register("vertex_merge")
@dataclasses.dataclass
class MergeVertex(BaseVertex):
    """Concatenate along the feature (last) axis (reference
    ``MergeVertex.java`` merges along dimension 1 = channels/features; our
    layouts keep features last)."""

    def output_type(self, *input_types: InputType) -> InputType:
        first = input_types[0]
        if first.kind == "ff":
            return _inputs.feed_forward(sum(t.size for t in input_types))
        if first.kind == "recurrent":
            return _inputs.recurrent(sum(t.size for t in input_types),
                                     first.timesteps)
        if first.kind == "cnn":
            return _inputs.convolutional(
                first.height, first.width,
                sum(t.channels for t in input_types))
        raise ValueError(f"MergeVertex cannot merge {first.kind}")

    def apply(self, *xs: Array, masks=None) -> Array:
        return jnp.concatenate(xs, axis=-1)


@serde.register("vertex_elementwise")
@dataclasses.dataclass
class ElementWiseVertex(BaseVertex):
    """Pointwise combine (reference ``ElementWiseVertex.java``; ops Add,
    Subtract, Product; Average/Max added by later reference versions kept
    for completeness)."""

    op: str = "add"

    def apply(self, *xs: Array, masks=None) -> Array:
        op = self.op.lower()
        if op == "add":
            out = xs[0]
            for x in xs[1:]:
                out = out + x
            return out
        if op == "subtract":
            if len(xs) != 2:
                raise ValueError("subtract needs exactly 2 inputs")
            return xs[0] - xs[1]
        if op == "product":
            out = xs[0]
            for x in xs[1:]:
                out = out * x
            return out
        if op == "average":
            return sum(xs) / len(xs)
        if op == "max":
            out = xs[0]
            for x in xs[1:]:
                out = jnp.maximum(out, x)
            return out
        raise ValueError(f"Unknown elementwise op '{self.op}'")


@serde.register("vertex_subset")
@dataclasses.dataclass
class SubsetVertex(BaseVertex):
    """Feature slice [from, to] inclusive (reference ``SubsetVertex.java``)."""

    from_index: int = 0
    to_index: int = 0

    def output_type(self, *input_types: InputType) -> InputType:
        n = self.to_index - self.from_index + 1
        it = input_types[0]
        if it.kind == "recurrent":
            return _inputs.recurrent(n, it.timesteps)
        return _inputs.feed_forward(n)

    def apply(self, *xs: Array, masks=None) -> Array:
        return xs[0][..., self.from_index:self.to_index + 1]


@serde.register("vertex_stack")
@dataclasses.dataclass
class StackVertex(BaseVertex):
    """Concatenate along the batch axis (reference ``StackVertex.java``,
    used for weight-shared multi-branch input)."""

    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *xs: Array, masks=None) -> Array:
        return jnp.concatenate(xs, axis=0)


@serde.register("vertex_unstack")
@dataclasses.dataclass
class UnstackVertex(BaseVertex):
    """Take batch slice ``from_index`` of ``stack_size`` equal chunks
    (reference ``UnstackVertex.java``)."""

    from_index: int = 0
    stack_size: int = 1

    def apply(self, *xs: Array, masks=None) -> Array:
        x = xs[0]
        step = x.shape[0] // self.stack_size
        return x[self.from_index * step:(self.from_index + 1) * step]


@serde.register("vertex_scale")
@dataclasses.dataclass
class ScaleVertex(BaseVertex):
    """Multiply by a fixed scalar (reference ``ScaleVertex.java``)."""

    scale_factor: float = 1.0

    def apply(self, *xs: Array, masks=None) -> Array:
        return xs[0] * self.scale_factor


@serde.register("vertex_shift")
@dataclasses.dataclass
class ShiftVertex(BaseVertex):
    """Add a fixed scalar (reference ``ShiftVertex.java``)."""

    shift_factor: float = 0.0

    def apply(self, *xs: Array, masks=None) -> Array:
        return xs[0] + self.shift_factor


@serde.register("vertex_preprocessor")
@dataclasses.dataclass
class PreprocessorVertex(BaseVertex):
    """Standalone input preprocessor (reference ``PreprocessorVertex.java``)."""

    preprocessor: Optional[object] = None

    def output_type(self, *input_types: InputType) -> InputType:
        return self.preprocessor.output_type(input_types[0])

    def apply(self, *xs: Array, masks=None) -> Array:
        return self.preprocessor(xs[0])


@serde.register("vertex_l2")
@dataclasses.dataclass
class L2Vertex(BaseVertex):
    """Pairwise L2 distance between two activations (reference
    ``L2Vertex.java``, used by siamese/triplet setups)."""

    eps: float = 1e-8

    def output_type(self, *input_types: InputType) -> InputType:
        return _inputs.feed_forward(1)

    def apply(self, *xs: Array, masks=None) -> Array:
        a, b = xs
        d = (a - b).reshape(a.shape[0], -1)
        return jnp.sqrt(jnp.sum(d * d, axis=1, keepdims=True) + self.eps)


@serde.register("vertex_l2_normalize")
@dataclasses.dataclass
class L2NormalizeVertex(BaseVertex):
    """Normalize activations to unit L2 norm (reference
    ``L2NormalizeVertex.java``)."""

    eps: float = 1e-8

    def apply(self, *xs: Array, masks=None) -> Array:
        x = xs[0]
        flat = x.reshape(x.shape[0], -1)
        norm = jnp.sqrt(jnp.sum(flat * flat, axis=1) + self.eps)
        return x / norm.reshape((-1,) + (1,) * (x.ndim - 1))


@serde.register("vertex_last_time_step")
@dataclasses.dataclass
class LastTimeStepVertex(BaseVertex):
    """(batch, time, f) -> (batch, f) at the last *unmasked* step (reference
    ``rnn/LastTimeStepVertex.java``; ``mask_input`` names the network input
    whose mask identifies sequence ends)."""

    mask_input: Optional[str] = None

    def output_type(self, *input_types: InputType) -> InputType:
        return _inputs.feed_forward(input_types[0].size)

    def apply(self, *xs: Array, masks=None) -> Array:
        x = xs[0]
        mask = None if masks is None else masks.get(self.mask_input)
        if mask is None:
            return x[:, -1]
        idx = jnp.sum(mask > 0, axis=1).astype(jnp.int32) - 1
        idx = jnp.clip(idx, 0, x.shape[1] - 1)
        return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]


@serde.register("vertex_duplicate_to_time_series")
@dataclasses.dataclass
class DuplicateToTimeSeriesVertex(BaseVertex):
    """(batch, f) -> (batch, time, f), broadcast along the time axis of a
    reference input (reference ``rnn/DuplicateToTimeSeriesVertex.java``)."""

    reference_input: Optional[str] = None

    def output_type(self, *input_types: InputType) -> InputType:
        return _inputs.recurrent(input_types[0].flat_size())

    def apply(self, *xs: Array, masks=None, timesteps: Optional[int] = None
              ) -> Array:
        x = xs[0]
        if timesteps is None:
            raise ValueError("DuplicateToTimeSeriesVertex needs the "
                             "reference input's timestep count")
        return jnp.broadcast_to(x[:, None, :],
                                (x.shape[0], timesteps, x.shape[1]))


@serde.register("vertex_stream_expand")
@dataclasses.dataclass
class StreamExpandVertex(BaseVertex):
    """(batch, time, f) -> (batch, time, n_streams, f): a residual path
    of ``n_streams`` streams enters as that many copies of the
    embedding (hyper-connections, ``nn/layers/decoder.py``)."""

    n_streams: int = 4

    def apply(self, *xs: Array, masks=None) -> Array:
        x = xs[0]
        return jnp.broadcast_to(
            x[:, :, None, :], x.shape[:2] + (self.n_streams, x.shape[2]))


@serde.register("vertex_stream_sum")
@dataclasses.dataclass
class StreamSumVertex(BaseVertex):
    """(batch, time, n_streams, f) -> (batch, time, f): the streams
    leave the residual path as their sum (taken in float32)."""

    def apply(self, *xs: Array, masks=None) -> Array:
        x = xs[0]
        return jnp.sum(x, axis=2, dtype=jnp.promote_types(
            x.dtype, jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------- configuration
@serde.register("computation_graph_conf")
@dataclasses.dataclass
class ComputationGraphConfiguration:
    """Reference ``ComputationGraphConfiguration``: named DAG + global conf."""

    conf: object = None                      # GlobalConfig
    network_inputs: List[str] = dataclasses.field(default_factory=list)
    network_outputs: List[str] = dataclasses.field(default_factory=list)
    vertices: Dict[str, BaseVertex] = dataclasses.field(default_factory=dict)
    backprop: bool = True
    pretrain: bool = False
    backprop_type: str = "standard"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 0
    input_types: Optional[List[object]] = None

    # topo order is derived, not serialized redundantly but recomputed
    def topological_order(self) -> List[str]:
        """Kahn's algorithm over vertex names (reference
        ``topologicalSortOrder():850``); deterministic (insertion order
        tie-break)."""
        indeg = {name: 0 for name in self.vertices}
        dependents: Dict[str, List[str]] = {n: [] for n in self.vertices}
        for name, v in self.vertices.items():
            for inp in v.inputs:
                if inp in self.vertices:
                    indeg[name] += 1
                    dependents[inp].append(name)
                elif inp not in self.network_inputs:
                    raise ValueError(
                        f"Vertex '{name}' consumes unknown input '{inp}'")
        queue = [n for n, d in indeg.items() if d == 0]
        order: List[str] = []
        while queue:
            n = queue.pop(0)
            order.append(n)
            for dep in dependents[n]:
                indeg[dep] -= 1
                if indeg[dep] == 0:
                    queue.append(dep)
        if len(order) != len(self.vertices):
            cyclic = sorted(set(self.vertices) - set(order))
            raise ValueError(f"Graph has a cycle involving {cyclic}")
        return order

    # ---- JSON round-trip -------------------------------------------------
    def to_dict(self) -> dict:
        return serde.to_dict(self)

    def to_json(self, indent: Optional[int] = 2) -> str:
        import json
        return json.dumps(self.to_dict(), indent=indent)

    @staticmethod
    def from_dict(d: dict) -> "ComputationGraphConfiguration":
        return serde.from_dict(d)

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        import json
        return ComputationGraphConfiguration.from_dict(json.loads(s))

    def to_yaml(self) -> str:
        """Reference ``ComputationGraphConfiguration.toYaml``."""
        import yaml
        return yaml.safe_dump(self.to_dict(), sort_keys=False)

    @staticmethod
    def from_yaml(s: str) -> "ComputationGraphConfiguration":
        import yaml
        return ComputationGraphConfiguration.from_dict(yaml.safe_load(s))


class GraphBuilder:
    """Reference ``ComputationGraphConfiguration.GraphBuilder`` fluent API."""

    def __init__(self, global_conf):
        self._cgc = ComputationGraphConfiguration(conf=global_conf)

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._cgc.network_inputs.extend(names)
        return self

    def add_layer(self, name: str, layer: BaseLayerConfig,
                  *inputs: str, preprocessor=None) -> "GraphBuilder":
        """Reference ``addLayer(name, layer, [preprocessor,] inputs...)``."""
        self._cgc.vertices[name] = LayerVertex(
            inputs=list(inputs), layer=layer, preprocessor=preprocessor)
        return self

    # reference alias
    layer = add_layer

    def add_vertex(self, name: str, vertex: BaseVertex,
                   *inputs: str) -> "GraphBuilder":
        vertex.inputs = list(inputs)
        self._cgc.vertices[name] = vertex
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._cgc.network_outputs = list(names)
        return self

    def set_input_types(self, *input_types) -> "GraphBuilder":
        self._cgc.input_types = list(input_types)
        return self

    def backprop_type(self, kind: str) -> "GraphBuilder":
        self._cgc.backprop_type = kind.lower()
        return self

    def t_bptt_forward_length(self, n: int) -> "GraphBuilder":
        self._cgc.tbptt_fwd_length = int(n)
        return self

    def t_bptt_backward_length(self, n: int) -> "GraphBuilder":
        self._cgc.tbptt_back_length = int(n)
        return self

    def pretrain(self, flag: bool) -> "GraphBuilder":
        self._cgc.pretrain = flag
        return self

    def backprop(self, flag: bool) -> "GraphBuilder":
        self._cgc.backprop = flag
        return self

    def build(self) -> ComputationGraphConfiguration:
        cgc = self._cgc
        if not cgc.network_inputs:
            raise ValueError("addInputs() never called")
        if not cgc.network_outputs:
            raise ValueError("setOutputs() never called")
        for out in cgc.network_outputs:
            if out not in cgc.vertices:
                raise ValueError(f"Output '{out}' is not a vertex")
        defaults = cgc.conf.layer_defaults()
        for v in cgc.vertices.values():
            if isinstance(v, LayerVertex) and v.layer is not None:
                v.layer.finalize_defaults(defaults)
        if cgc.input_types is not None:
            _infer_graph_shapes(cgc)
        cgc.topological_order()  # validates acyclicity + unknown inputs
        from .validation import validate_computation_graph_configuration
        validate_computation_graph_configuration(cgc)
        return cgc


def _infer_graph_shapes(cgc: ComputationGraphConfiguration) -> None:
    """Propagate InputTypes through the DAG in topo order, setting each
    layer's n_in and auto-inserting family preprocessors (reference
    ``GraphBuilder.setInputTypes`` + ``addPreProcessors``)."""
    from .neural_net_configuration import _layer_input_kind, _preprocessor_for

    if len(cgc.input_types) != len(cgc.network_inputs):
        raise ValueError(
            f"{len(cgc.network_inputs)} inputs but "
            f"{len(cgc.input_types)} input types")
    types: Dict[str, InputType] = dict(zip(cgc.network_inputs,
                                           cgc.input_types))
    for name in cgc.topological_order():
        v = cgc.vertices[name]
        in_types = [types[i] for i in v.inputs]
        if isinstance(v, LayerVertex):
            it = in_types[0]
            if v.preprocessor is None:
                pp = _preprocessor_for(it, _layer_input_kind(v.layer))
                if pp is not None:
                    v.preprocessor = pp
            if v.preprocessor is not None:
                it = v.preprocessor.output_type(it)
            v.layer.set_n_in(it)
            types[name] = v.layer.output_type(it)
        else:
            types[name] = v.output_type(*in_types)
    cgc._inferred_types = types
