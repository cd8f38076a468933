"""The per-layer description: the one seam between a model and the
serving engine.

A model that wants to be served implements two methods and nothing else:

  serving_description() -> ModelDescription
      what every layer IS: its attention kind and shapes, its FFN kind
      and shapes (the dataclasses below, all static and hashable);
  serving_parameters()  -> {"emb", "norm", "head", "layers": [{...}]}
      its parameters (Layer parameters, possibly lazy) under the
      engine's canonical names: `ln1 wo`, `ln2` (a PARALLEL layer,
      `LayerSpec.parallel`, has none: its FFN reads what its projections
      read) and either `wq wk wv` or one fused `wqkv` ([hidden, q | k |
      v] columns); `sink` ([heads]) where the layer has one; a dense FFN
      as `wg wu wd`; routed experts as `router` ([hidden, experts]),
      `router_bias` ([experts], only where the router stores one), `w_gu`
      ([held, hidden, 2 x width], gate columns first) and `w_d` ([held,
      width, hidden]) and, beside them, ONE shared SwiGLU as `ws_g ws_u
      ws_d` of `FFNSpec.shared_width` (n shared experts that are summed
      or averaged are one SwiGLU of n times the width, the 1/n in the
      down projection's rows: a layout of the weights). Norm weights are
      an RMSNorm's or, under `ModelDescription.norm` "layer", a
      mean-subtracting LayerNorm's without bias. Rotation is half-split
      (dims i and i + rope_dim / 2 pair); a checkpoint that pairs
      (2i, 2i + 1) hands `wq` / `wk` over with each head's columns
      de-interleaved; `AttentionSpec.rope_dim` 0 is a position-free
      layer. A LATENT attention layer (`AttentionSpec.latent`) has no
      `wq wk wv`: it gives `wq_a` ([hidden, query rank]), `q_norm`,
      `wq_b` ([query rank, heads x (no-position + rotary width)], a
      head's no-position columns first), `wkv_a` ([hidden, latent rank +
      rotary width]), `kv_norm`, the up-projection split by use as
      `w_uk` ([latent rank, heads x no-position width]) and `w_uv`
      ([latent rank, heads x value width]); `w_gate` ([hidden, heads])
      where the layer gates its heads; and an indexer's `ix_wq` ([query
      rank, index heads x index width]), `ix_wk` ([hidden, index
      width]), `ix_kn_w ix_kn_b` (the index key's LayerNorm) and `ix_ww`
      ([hidden, index heads]). A PER-HEAD layer may have an indexer too
      (`wq wk wv` and the same `ix_*`, with `ix_wq` [hidden, index heads
      x index width]: its index query reads the normed hidden state),
      and `q_hn k_hn` ([head width] each) where `AttentionSpec.qk_norm`
      norms every query and key head before the rotation. A router with
      `FFNSpec.score` "softmax" has no `router_bias`. `head` is a matrix
      of its own ([hidden, vocab]); a model with tied embeddings hands
      over the transpose. Matrices are [in, out].

The engine (serving.py, scheduler.py) reads the description and the
canonical names and never asks what class the model is. What a
description asks for that a path cannot do yet raises
UnsupportedByDescription when the engine is BUILT — never a wrong answer
later.
"""
import dataclasses
from typing import NamedTuple, Optional, Tuple


class UnsupportedByDescription(ValueError):
    """The model's layer description asks for something this engine
    option (or combination) cannot serve yet."""


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """Latent (low-rank) attention: ONE cached row a token, [c_kv ; k_r]
    = the normed, rescaled latent and the rotated position key, shared
    by every head. `AttentionSpec.qk_dim` is then no-position + rotary
    width and `rope_dim` the TRAILING dims of a query head that rotate."""
    q_rank: int
    kv_rank: int
    q_scale: float = 1.0            # c_q is multiplied after its norm
    kv_scale: float = 1.0           # c_kv likewise

    def row_width(self, rope_dim):
        return self.kv_rank + rope_dim


@dataclasses.dataclass(frozen=True)
class IndexerSpec:
    """Learned sparse attention: a query attends to the `top_k` visible
    positions with the largest index score (all of them while fewer are
    visible). One index key a token is cached beside the layer's rows.
    The index query is a product of the query latent in a latent layer
    and of the normed hidden state in a per-head one; both rotate on the
    layer's base."""
    n_heads: int
    dim: int
    rope_dim: int                   # leading dims of q^I and k^I that rotate
    top_k: int
    eps: float = 1e-6               # of the index key's LayerNorm


class GroupKey(NamedTuple):
    """What a page group's layers have in common. `kind` "heads": per-head
    K and V, `n_kv_heads` of widths `qk_dim` / `v_dim` a token; "latent":
    ONE row a token for all heads, `qk_dim` wide, no values. Either kind
    keeps an `index_width`-wide index key a token beside them where its
    layers have an indexer (0: none)."""
    kind: str
    n_kv_heads: int
    qk_dim: int
    v_dim: int
    window: Optional[int]
    index_width: int = 0


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    n_heads: int
    n_kv_heads: int
    qk_dim: int                     # width of a query / key head
    v_dim: int                      # width of a value head
    rope_dim: int                   # leading dims of q and k that
    #                                 rotate; 0 = a position-free layer
    rope_theta: float
    window: Optional[int] = None    # None = full causal attention
    sink: bool = False              # learned per-head sink logit
    value_scale: float = 1.0        # values are scaled before caching
    latent: Optional[LatentSpec] = None
    gate: bool = False              # head-wise sigmoid gate before wo
    indexer: Optional[IndexerSpec] = None
    qk_norm: bool = False           # RMSNorm on every q and k head

    @property
    def group(self):
        """Layers with equal keys share a page pool shape, a page table
        and a freeing policy."""
        index_width = self.indexer.dim if self.indexer else 0
        if self.latent is not None:
            return GroupKey("latent", 1,
                            self.latent.row_width(self.rope_dim), 0,
                            self.window, index_width)
        return GroupKey("heads", self.n_kv_heads, self.qk_dim, self.v_dim,
                        self.window, index_width)


@dataclasses.dataclass(frozen=True)
class FFNSpec:
    kind: str                       # "dense" | "experts"
    width: int                      # SwiGLU width (of ONE expert)
    n_experts: int = 0              # router outputs (all chips' experts)
    top_k: int = 0
    held: Tuple[int, int] = (0, 0)  # [lo, hi): the experts held HERE
    shared_width: int = 0           # one shared SwiGLU beside them (n
    #                                 summed experts: n x their width)
    score: str = "sigmoid"          # the router (ops/moe.route): "sigmoid"
    #                                 (+ a stored bias, if any) | "softmax"


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    attn: AttentionSpec
    ffn: FFNSpec
    # a PARALLEL block: y = x + Attn(n) + FFN(n) with ONE norm n =
    # norm(x); the default is sequential, the FFN norming x + Attn
    parallel: bool = False


@dataclasses.dataclass(frozen=True)
class ModelDescription:
    hidden_size: int
    vocab_size: int
    eps: float
    layers: Tuple[LayerSpec, ...]
    norm: str = "rms"               # every layer norm and the final one:
    #                                 "rms" | "layer" (mean-subtracted,
    #                                 weight only)

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
                and self.norm == "rms" and not first.parallel
                and first.ffn.kind == "dense"
                and a.qk_dim == a.v_dim == a.rope_dim
                and a.n_heads * a.qk_dim == self.hidden_size
                and a.window is None and not a.sink
                and a.value_scale == 1.0
                and a.latent is None and not a.gate
                and a.indexer is None and not a.qk_norm)

    @property
    def has_experts(self):
        return any(layer.ffn.kind == "experts" for layer in self.layers)

    @property
    def has_indexer(self):
        return any(layer.attn.indexer is not None for layer in self.layers)


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
