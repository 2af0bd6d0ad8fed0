"""Device mesh + parameter sharding rules.

The reference's only intra-model parallelism is accelerate's
``device_map="auto"`` layer offloading (compare_base_vs_instruct.py:424-435);
its only "communication backend" is the OpenAI Batch REST API (SURVEY.md §5).
The TPU-native replacement is declarative: build a ``jax.sharding.Mesh`` over
the slice, annotate params/activations with ``NamedSharding``, and let XLA
emit the all-gather/reduce-scatter/psum collectives over ICI.

Axes (scaling-book convention):
- ``data``  — the perturbation/question grid (batch) axis.
- ``model`` — tensor parallelism: attention heads / MLP columns / vocab.
- ``seq``   — sequence (context) parallelism for the long-context path
  (parallel/ring_attention.py).

Megatron-style rules: qkv projections are column-parallel (heads), the
attention output and MLP down projection row-parallel, embeddings sharded on
the hidden axis, the LM head on vocab. Families whose head counts don't
divide the mesh (falcon-7b MQA: 71 q heads, 1 kv head) degrade gracefully to
replicated attention + sharded MLP rather than failing.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import MeshConfig
from ..models.registry import ModelConfig

Params = Dict[str, Any]

# (regex over '/'-joined param paths, PartitionSpec) — the rule shape of
# the fleet's per-model registry (SNIPPETS.md [2] match_partition_rules
# is the exemplar). First match wins; scalar leaves always replicate.
PartitionRules = Sequence[Tuple[str, P]]


def build_mesh(cfg: MeshConfig, devices=None) -> Mesh:
    """Create a (data, model, seq) mesh. Works on real TPU slices and on
    virtual CPU devices (XLA_FLAGS=--xla_force_host_platform_device_count=N)."""
    if devices is None:
        devices = jax.devices()
    n = cfg.n_devices
    if n > len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(cfg.shape)
    return Mesh(arr, cfg.axis_names)


def replica_devices(index: int, per_replica: int = 1, devices=None) -> list:
    """The devices replica ``index`` of an in-process replica set lives
    on (``serve --replicas N``): ``per_replica`` consecutive devices
    (the replica's mesh size, 1 without a mesh) starting at
    ``index * per_replica``, so replica i of four one-chip replicas sits
    on chip i. A host with fewer devices than the set needs wraps around
    — a one-device CPU host serves every replica from it."""
    devices = list(jax.devices() if devices is None else devices)
    if per_replica > len(devices):
        raise ValueError(f"a replica needs {per_replica} devices, have "
                         f"{len(devices)}")
    slots = len(devices) // per_replica
    start = (index % slots) * per_replica
    return devices[start:start + per_replica]


def single_device_mesh() -> Mesh:
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                ("data", "model", "seq"))


def decoder_param_specs(cfg: ModelConfig, mesh: Mesh) -> Params:
    """PartitionSpec tree matching models/decoder.py's param layout.

    Head-sharded attention requires n_heads % model_size == 0 AND
    n_kv_heads % model_size == 0 (MQA/odd-head families replicate attention
    instead); MLP sharding requires intermediate_size % model_size == 0.
    """
    m = mesh.shape["model"]
    shard_attn = (cfg.n_heads % m == 0) and (cfg.n_kv_heads % m == 0)
    shard_mlp = cfg.intermediate_size % m == 0
    shard_vocab = cfg.vocab_size % m == 0
    shard_hidden = cfg.hidden_size % m == 0

    A = "model" if shard_attn else None    # qkv output / wo input axis
    F = "model" if shard_mlp else None     # MLP hidden axis
    V = "model" if shard_vocab else None   # vocab axis

    layers: Params = {
        "ln1": {"scale": P(None, None)},
        "wq": P(None, None, A), "wk": P(None, None, A), "wv": P(None, None, A),
        "wo": P(None, A, None),
        "w_up": P(None, None, F), "w_down": P(None, F, None),
    }
    if cfg.norm == "layernorm":
        layers["ln1"]["bias"] = P(None, None)
    if not cfg.shared_block_ln:
        layers["ln2"] = dict(layers["ln1"])
    if cfg.gated_mlp:
        layers["w_gate"] = P(None, None, F)
    if cfg.qkv_bias:
        layers.update({"bq": P(None, A), "bk": P(None, A), "bv": P(None, A)})
    if cfg.attn_out_bias:
        layers["bo"] = P(None, None)
    if cfg.mlp_bias:
        layers.update({"b_up": P(None, F), "b_down": P(None, None)})

    specs: Params = {
        # Embedding sharded on hidden: the take() stays local, layer 0's
        # first matmul all-gathers activations (cheap at these batch sizes).
        "tok_embed": P(None, "model" if shard_hidden else None),
        "layers": layers,
    }
    if cfg.pos_embedding == "learned":
        specs["pos_embed"] = P(None, "model" if shard_hidden else None)
    if cfg.embedding_norm:
        specs["embed_ln"] = {"scale": P(None), "bias": P(None)}
    if cfg.final_norm:
        specs["final_ln"] = {"scale": P(None)}
        if cfg.norm == "layernorm":
            specs["final_ln"]["bias"] = P(None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, V)
    return specs


def encdec_param_specs(cfg, mesh: Mesh) -> Params:
    """PartitionSpec tree matching models/encdec.py's T5 param layout —
    Megatron-style: attention head projections column-parallel (output
    axis on 'model'), their output projections row-parallel, MLP columns
    on 'model'; relative-attention bucket embeddings shard on the HEAD
    axis so the per-head bias lives with its heads. Same divisibility
    degradations as decoder_param_specs (non-dividing axes replicate).

    Closes the round-2 gap where `--mesh` was silently ignored for
    encoder-decoder checkpoints (models/factory.py; the reference runs
    T0-3B/tk-instruct-3b 8-bit on one GPU,
    compare_instruct_models.py:145-166,471-475 — at bf16 they need the
    slice)."""
    m = mesh.shape["model"]
    shard_attn = cfg.n_heads % m == 0
    A = "model" if shard_attn else None
    F = "model" if cfg.intermediate_size % m == 0 else None

    def stack(cross: bool) -> Params:
        p: Params = {
            "ln_attn": P(None, None),
            "wq": P(None, None, A), "wk": P(None, None, A),
            "wv": P(None, None, A), "wo": P(None, A, None),
            "ln_mlp": P(None, None),
            "wo_mlp": P(None, F, None),
        }
        if cfg.gated_mlp:
            p.update({"wi_0": P(None, None, F), "wi_1": P(None, None, F)})
        else:
            p["wi"] = P(None, None, F)
        if cross:
            p.update({
                "ln_cross": P(None, None),
                "cq": P(None, None, A), "ck": P(None, None, A),
                "cv": P(None, None, A), "co": P(None, A, None),
            })
        return p

    specs: Params = {
        "shared_embed": P(None, "model" if cfg.hidden_size % m == 0 else None),
        "enc_rel_embed": P(None, A),
        "dec_rel_embed": P(None, A),
        "encoder": stack(cross=False),
        "enc_final_ln": P(None),
        "decoder": stack(cross=True),
        "dec_final_ln": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(
            None, "model" if cfg.vocab_size % m == 0 else None)
    return specs


# ---------------------------------------------------------------------------
# Per-model partition-rule registry (the fleet layer's seam)
# ---------------------------------------------------------------------------

# Model-name pattern -> rules factory. A factory takes (cfg, mesh) and
# returns EITHER a full PartitionSpec pytree matching the param tree, OR
# a PartitionRules sequence to be matched against '/'-joined param paths
# (match_partition_rules). Registered rules win over the structural
# defaults (decoder_param_specs / encdec_param_specs), so one
# odd-architecture model in a fleet can shard its own way without
# forking shard_params — and the weight streamer (models/weights.py)
# places every chunk under the SAME registry, so streamed and monolithic
# loads can never disagree on placement.
_PARTITION_RULE_REGISTRY: List[
    Tuple[str, Callable[[Any, Mesh], Any]]] = []


def register_partition_rules(
        name_pattern: str,
        rules_fn: Callable[[Any, Mesh], Any]) -> None:
    """Register per-model partition rules: ``name_pattern`` is a regex
    matched (re.search) against ``cfg.name``. Later registrations win
    over earlier ones (override in tests / deployment preludes)."""
    _PARTITION_RULE_REGISTRY.insert(0, (str(name_pattern), rules_fn))


def unregister_partition_rules(name_pattern: str) -> None:
    _PARTITION_RULE_REGISTRY[:] = [
        (p, f) for p, f in _PARTITION_RULE_REGISTRY if p != name_pattern]


def registered_rules_for(cfg) -> Optional[Callable[[Any, Mesh], Any]]:
    name = str(getattr(cfg, "name", ""))
    for pattern, fn in _PARTITION_RULE_REGISTRY:
        if re.search(pattern, name):
            return fn
    return None


def _tree_with_paths(params: Params) -> List[Tuple[str, Any]]:
    """('/'-joined path, leaf) pairs; QuantTensor is a leaf (its q/scale
    split is derived, not matched)."""
    from ..models.quant import QuantTensor

    flat = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, QuantTensor))[0]
    out = []
    for path, leaf in flat:
        parts = []
        for k in path:
            parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
        out.append(("/".join(parts), leaf))
    return out


def match_partition_rules(rules: PartitionRules, params: Params) -> Params:
    """PartitionSpec pytree for ``params`` from (regex, spec) rules —
    the SNIPPETS.md [2] exemplar adapted to this engine's dict pytrees:
    first re.search match on the '/'-joined path wins, scalar leaves
    always replicate, and an unmatched non-scalar leaf is a loud error
    (a silently replicated 7B matrix is an OOM at 3am, not a default).
    """
    from ..models.quant import QuantTensor

    def spec_for(name: str, leaf) -> P:
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                return spec
        raise ValueError(f"partition rule not found for param: {name}")

    leaves = [spec_for(name, leaf) for name, leaf in _tree_with_paths(params)]
    treedef = jax.tree_util.tree_structure(
        params, is_leaf=lambda x: isinstance(x, QuantTensor))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def spec_tree_for(cfg, mesh: Mesh, params: Optional[Params] = None
                  ) -> Params:
    """The PartitionSpec pytree for one model on one mesh — registry
    first (per-model rules), structural defaults otherwise. This is the
    ONE resolution path: shard_params (monolithic load) and
    models/weights.stream_params (chunked fleet load) both call it, so
    a model's placement cannot depend on how its weights arrived."""
    from ..models.registry import T5Config

    fn = registered_rules_for(cfg)
    if fn is not None:
        rules = fn(cfg, mesh)
        if isinstance(rules, (list, tuple)):
            if params is None:
                raise ValueError(
                    "rule-list partition rules need the param tree to "
                    "match against (pass params=)")
            return match_partition_rules(rules, params)
        return rules
    return (encdec_param_specs(cfg, mesh) if isinstance(cfg, T5Config)
            else decoder_param_specs(cfg, mesh))


def quant_scale_spec(spec: P) -> P:
    """Spec for a QuantTensor's per-output-channel scale, derived from the
    dense weight's spec: keep the leading (layer-stack) axes, keep the OUTPUT
    axis. Column-parallel weights (output axis sharded on 'model') get
    model-sharded scales; row-parallel weights (input axis sharded) have
    per-output scales that are replicated — exactly the bitsandbytes-on-
    multi-GPU composition the reference ran (compare_base_vs_instruct.py:
    424-435: load_in_8bit + device_map='auto')."""
    return P(*spec[:-2], spec[-1])


def shard_params(params: Params, cfg: ModelConfig, mesh: Mesh) -> Params:
    """device_put every param with its NamedSharding (single host).

    int8 trees compose: a QuantTensor's payload takes the dense weight's
    spec, its scale the derived output-axis spec (quant_scale_spec).
    Resolution goes through spec_tree_for — per-model registry rules
    first, then the structural defaults (T5Config trees get the enc-dec
    specs)."""
    from ..models.quant import QuantTensor

    specs = spec_tree_for(cfg, mesh, params)

    def place(leaf, spec):
        if isinstance(leaf, QuantTensor):
            return QuantTensor(
                q=jax.device_put(leaf.q, NamedSharding(mesh, spec)),
                scale=jax.device_put(
                    leaf.scale, NamedSharding(mesh, quant_scale_spec(spec))),
                dynamic=leaf.dynamic,
            )
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(place, params, specs,
                        is_leaf=lambda x: isinstance(x, QuantTensor))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Inputs: grid/batch axis over 'data', sequence axis replicated."""
    return NamedSharding(mesh, P("data", None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
