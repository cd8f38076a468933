"""The per-layer description: the one seam between a model and the
serving engine.

A model that wants to be served implements two methods and nothing else:

  serving_description() -> ModelDescription
      what every layer IS: its attention kind and shapes, its FFN kind
      and shapes (the dataclasses below, all static and hashable);
  serving_parameters()  -> {"emb", "norm", "head", "layers": [{...}]}
      its parameters (Layer parameters, possibly lazy) under the
      engine's canonical names: `ln1 ln2 wo` and either `wq wk wv` or one
      fused `wqkv` ([hidden, q | k | v] columns); `sink` ([heads]) where
      the layer has one; a dense FFN as `wg wu wd`; routed experts as
      `router` ([hidden, experts]), `router_bias` ([experts]), `w_gu`
      ([held, hidden, 2 x width], gate columns first) and `w_d` ([held,
      width, hidden]). Matrices are [in, out].

The engine (serving.py, scheduler.py) reads the description and the
canonical names and never asks what class the model is. What a
description asks for that a path cannot do yet raises
UnsupportedByDescription when the engine is BUILT — never a wrong answer
later.
"""
import dataclasses
from typing import Optional, Tuple


class UnsupportedByDescription(ValueError):
    """The model's layer description asks for something this engine
    option (or combination) cannot serve yet."""


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    n_heads: int
    n_kv_heads: int
    qk_dim: int                     # width of a query / key head
    v_dim: int                      # width of a value head
    rope_dim: int                   # leading dims of q and k that rotate
    rope_theta: float
    window: Optional[int] = None    # None = full causal attention
    sink: bool = False              # learned per-head sink logit
    value_scale: float = 1.0        # values are scaled before caching

    @property
    def group(self):
        """Layers with equal keys share a page pool shape, a page table
        and a freeing policy."""
        return (self.n_kv_heads, self.qk_dim, self.v_dim, self.window)


@dataclasses.dataclass(frozen=True)
class FFNSpec:
    kind: str                       # "dense" | "experts"
    width: int                      # SwiGLU width (of ONE expert)
    n_experts: int = 0              # router outputs (all chips' experts)
    top_k: int = 0
    held: Tuple[int, int] = (0, 0)  # [lo, hi): the experts held HERE


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    attn: AttentionSpec
    ffn: FFNSpec


@dataclasses.dataclass(frozen=True)
class ModelDescription:
    hidden_size: int
    vocab_size: int
    eps: float
    layers: Tuple[LayerSpec, ...]

    @property
    def groups(self):
        """The distinct attention groups, in order of first appearance."""
        seen = []
        for layer in self.layers:
            if layer.attn.group not in seen:
                seen.append(layer.attn.group)
        return tuple(seen)

    @property
    def layer_group(self):
        """Index into `groups` for every layer."""
        groups = self.groups
        return tuple(groups.index(layer.attn.group)
                     for layer in self.layers)

    @property
    def plain(self):
        """Every layer the same dense block with full rotary causal
        attention and equal key and value widths: what every engine mode
        (megakernel, fused blocks, speculation, tensor parallelism, KV
        export, adapters, prefix sharing) was written for."""
        first = self.layers[0]
        a = first.attn
        return (all(layer == first for layer in self.layers)
                and first.ffn.kind == "dense"
                and a.qk_dim == a.v_dim == a.rope_dim
                and a.n_heads * a.qk_dim == self.hidden_size
                and a.window is None and not a.sink
                and a.value_scale == 1.0)

    @property
    def has_experts(self):
        return any(layer.ffn.kind == "experts" for layer in self.layers)


def describe(model):
    """The model's description, or a TypeError that names the seam."""
    try:
        desc = model.serving_description()
        model.serving_parameters
    except AttributeError:
        raise TypeError(
            f"{type(model).__name__} does not describe itself for "
            "serving: a servable model implements serving_description() "
            "and serving_parameters() (paddle_tpu/inference/"
            "description.py)") from None
    if not isinstance(desc, ModelDescription):
        raise TypeError(
            f"{type(model).__name__}.serving_description() returned "
            f"{type(desc).__name__}, not a ModelDescription")
    return desc
