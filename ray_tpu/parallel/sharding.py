"""Parameter & activation sharding rules.

The reference shards with torch FSDP wrappers + megatron-style module
surgery; here sharding is declarative: a table of (param-path regex ->
PartitionSpec template) applied over the pytree. XLA then emits
all-gather/reduce-scatter over `fsdp`, all-reduce over `dp`, and the
megatron collectives over `tp` automatically.

Conventions for decoder transformers (ray_tpu/models/*):
  embed      (vocab, d)        -> P("tp", "fsdp")     vocab-sharded matmul
  attn qkv   (d, heads*hd)     -> P("fsdp", "tp")     column parallel
  attn out   (heads*hd, d)     -> P("tp", "fsdp")     row parallel
  mlp gate/up(d, ff)           -> P("fsdp", "tp")     column parallel
  mlp down   (ff, d)           -> P("tp", "fsdp")     row parallel
  norms      (d,)              -> P(None)             replicated
Activations: batch over ("dp","fsdp"), sequence over "sp", model dim
unsharded (tp acts on weights; XLA keeps activations tp-sharded between the
column/row pair without materializing the full hidden). Where the mesh has
tp > 1 (and no sp) and the sequence divides, the residual stream between a
block's projections is sharded over the sequence on "tp" as well, and the
projections run as collective_matmul.py's overlapped pair
(tp_matmul_route): the sum over tp is then a reduce-scatter and an
all-gather, each hidden behind its own matmul.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


Rule = Tuple[str, P]


DEFAULT_RULES: Sequence[Rule] = (
    # MoE experts first: their paths can also contain generic names like
    # gate_proj, and first-match must pick the 3-axis ep spec.
    (r".*experts.*(gate|up).*kernel$", P("ep", "fsdp", "tp")),
    (r".*experts.*down.*kernel$", P("ep", "tp", "fsdp")),
    (r".*router.*kernel$", P("fsdp", None)),
    # Vocab-parallel embedding: vocab over (tp, fsdp), d_model UNSHARDED.
    # Sharding d here looks free but isn't: the lookup gather propagates
    # the table's d-sharding into the residual stream, which then fights
    # the batch-sharded activations and XLA resolves it with an
    # "involuntary full rematerialization" (replicate + repartition) in
    # the backward. Vocab-only sharding keeps the gather a masked
    # local-gather + all-reduce and (for tied embeddings) makes the LM
    # head a standard megatron vocab-parallel matmul.
    (r".*(token_embed|embed_tokens|wte)\b.*embedding$",
     P(("tp", "fsdp"), None)),
    # untied output head: (d_model, vocab) column-parallel over vocab
    (r".*(lm_head|output_proj)\b.*kernel$", P("fsdp", "tp")),
    (r".*(wq|wk|wv|qkv|q_proj|k_proj|v_proj)\b.*kernel(_q)?$",
     P("fsdp", "tp")),
    (r".*(wo|o_proj|out_proj|attn_out)\b.*kernel(_q)?$",
     P("tp", "fsdp")),
    (r".*(gate_proj|up_proj|w1|w3|fc_in)\b.*kernel(_q)?$",
     P("fsdp", "tp")),
    (r".*(down_proj|w2|fc_out)\b.*kernel(_q)?$", P("tp", "fsdp")),
    (r".*(pos_embed|wpe)\b.*embedding$", P(None, "fsdp")),
    (r".*(norm|ln_f|ln_1|ln_2|layernorm).*$", P()),
    (r".*bias$", P()),
    (r".*scale$", P()),
)


@dataclasses.dataclass
class ShardingRules:
    rules: Sequence[Rule] = DEFAULT_RULES
    default: P = dataclasses.field(default_factory=P)

    def spec_for(self, path: str, shape: Tuple[int, ...],
                 mesh: Mesh) -> P:
        spec = self._match(path)
        return _clip_to_mesh(spec, shape, mesh)

    def _match(self, path: str) -> P:
        for pattern, spec in self.rules:
            if re.match(pattern, path):
                return spec
        return self.default


def _clip_to_mesh(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop axes not in the mesh / of size 1, and any axis that doesn't
    divide the dimension — falling back to replication for that dim."""
    axis_sizes = mesh.shape
    out = []
    for i, entry in enumerate(spec):
        if i >= len(shape):
            break
        dim = shape[i]
        names = entry if isinstance(entry, tuple) else (
            (entry,) if entry is not None else ())
        kept = []
        prod = 1
        for name in names:
            sz = axis_sizes.get(name, 1)
            if sz > 1 and dim % (prod * sz) == 0:
                kept.append(name)
                prod *= sz
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def partition_spec_for(path: str, shape: Tuple[int, ...], mesh: Mesh,
                       rules: Optional[ShardingRules] = None) -> P:
    return (rules or ShardingRules()).spec_for(path, shape, mesh)


def path_str(path) -> str:
    """Canonical '/'-joined string for a jax key path (shared by the rule
    table, optimizer masks, and state sharding)."""
    return _path_str(path)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def sharding_tree(params, mesh: Mesh,
                  rules: Optional[ShardingRules] = None):
    """Pytree of NamedSharding matching `params` leaves."""
    rules = rules or ShardingRules()

    def leaf_sharding(path, leaf):
        spec = rules.spec_for(_path_str(path), getattr(leaf, "shape", ()),
                              mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf_sharding, params)


def shard_pytree(params, mesh: Mesh, rules: Optional[ShardingRules] = None):
    """device_put every leaf onto its NamedSharding (host -> mesh)."""
    shardings = sharding_tree(params, mesh, rules)
    return jax.tree_util.tree_map(jax.device_put, params, shardings)


# ---- activation constraints ------------------------------------------------
# Models can't take a Mesh argument without threading it through every
# module, so the train step publishes the mesh here (trace-time only) and
# models pin their residual-stream activations against it. Without the
# pin, XLA propagates the embed table's fsdp sharding of d_model into the
# hidden states and the backward pays an involuntary full
# rematerialization re-sharding them against the batch-sharded residual.
_ACTIVE: "list[Optional[activation_mesh]]" = [None]


class activation_mesh:
    """Context manager: make `mesh` visible to constrain_activations
    during tracing of a step function. `tp_overlapped_matmuls` counts the
    projections that took tp_matmul_route's route while it was open,
    `remat_saved_residuals` the named values that models said their
    rematted blocks keep (count_saved_residuals)."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh
        self.tp_overlapped_matmuls = 0
        self.remat_saved_residuals = 0

    def __enter__(self):
        self._prev = _ACTIVE[0]
        _ACTIVE[0] = self
        return self

    def __exit__(self, *exc):
        _ACTIVE[0] = self._prev
        return False


def _mesh() -> Optional[Mesh]:
    return _ACTIVE[0].mesh if _ACTIVE[0] is not None else None


def _tp_seq_sharded(mesh: Mesh, shape: Tuple[int, ...]) -> bool:
    """Whether (B, S, D) activations of this shape live sharded over the
    sequence on `tp` between a block's projections: `tp` > 1 and the
    sequence divides. Under `sp` > 1 the sequence is sharded already and
    stays as it was (a ring over both axes is not built)."""
    tp = mesh.shape.get("tp", 1)
    return (len(shape) == 3 and tp > 1 and mesh.shape.get("sp", 1) == 1
            and shape[1] % tp == 0)


def tp_matmul_route(shape: Tuple[int, ...],
                    matmuls: int = 1) -> Optional[Mesh]:
    """The mesh on which `matmuls` tensor-parallel projections of (B, S,
    D) activations should run as parallel/collective_matmul.py's
    overlapped pair (the sequence sharded over `tp` on the side of the
    model dim), or None where they stay plain matmuls that the SPMD
    partitioner sums: outside an activation_mesh context, with `tp` of
    1, under `sp`, or where the sequence does not divide by `tp`. Chosen
    from the mesh and the shape alone; each route given is counted."""
    mesh = _mesh()
    if mesh is None or not _tp_seq_sharded(mesh, shape):
        return None
    _ACTIVE[0].tp_overlapped_matmuls += matmuls
    return mesh


def count_saved_residuals(n: int) -> None:
    """A model's word, while a step is traced, that a rematted block's
    policy keeps `n` values by name for the backward (models/llama.py);
    nothing outside an activation_mesh context."""
    if _ACTIVE[0] is not None:
        _ACTIVE[0].remat_saved_residuals += n


def constrain_activations(x, *, seq_axis: Optional[str] = "sp",
                          gathered: bool = False):
    """Pin (B, S, D) activations to batch over (dp, fsdp), sequence over
    sp, model dim replicated — the convention in this module's header.
    Where the blocks' projections take tp_matmul_route's route the
    residual stream's sequence is over `tp` as well; `gathered` asks for
    it whole on every `tp` device, as the head multiplies it. A no-op
    outside an activation_mesh context (single-device, serve)."""
    mesh = _mesh()
    if mesh is None or getattr(x, "ndim", 0) < 3:
        return x
    data = tuple(a for a in ("dp", "fsdp")
                 if mesh.shape.get(a, 1) > 1 and
                 x.shape[0] % mesh.shape[a] == 0)
    seq = (seq_axis if seq_axis and mesh.shape.get(seq_axis, 1) > 1
           and x.shape[1] % mesh.shape[seq_axis] == 0 else None)
    if not gathered and _tp_seq_sharded(mesh, x.shape):
        seq = "tp"
    spec = P(data if data else None, seq)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def attention_shard_spec(kv_shape: Tuple[int, ...]):
    """(mesh, spec) for (B, S, H, D) attention operands under the
    activation mesh — batch over (dp, fsdp), heads over tp, each only as
    far as it divides the k/v shape — or None outside an activation_mesh
    context and on a one-device mesh. For an op the SPMD partitioner
    cannot see into (a Pallas call is refused outright: "Mosaic kernels
    cannot be automatically partitioned"): the caller wraps it in
    jax.shard_map with this spec, and each device runs the kernel on its
    own rows and heads."""
    mesh = _mesh()
    if mesh is None or mesh.size == 1:
        return None
    return mesh, _clip_to_mesh(P(("dp", "fsdp"), None, "tp", None),
                               kv_shape, mesh)


def batch_sharding(mesh: Mesh, *, seq_axis: Optional[str] = "sp") -> NamedSharding:
    """Input batch (B, S, ...) sharded over data axes, seq over sp."""
    data = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    seq = (seq_axis if seq_axis and mesh.shape.get(seq_axis, 1) > 1
           else None)
    return NamedSharding(mesh, P(data if data else None, seq))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
