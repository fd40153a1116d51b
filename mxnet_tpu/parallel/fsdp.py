"""FSDP — fully-sharded data parallelism over the mesh's ``dp`` axis.

Reference semantics: none (MXNet 1.x shards nothing; ZeRO-1 in
``mesh.zero1_sharding`` shards only the optimizer moments).  The
TPU-native mechanism (SURVEY.md §2.4 extension, ROADMAP item 5): params
AND optimizer state live sharded over ``dp`` — per-device param+opt
bytes are exactly ÷dp — and the ONE jitted train step all-gathers each
weight on use and reduce-scatters its gradient straight into the
sharded optimizer update.  XLA GSPMD inserts both collectives from the
shardings alone; there is no hand-written gather/scatter, exactly like
the serving engine's tensor-parallel lowering (round 14).

The sharding story is the SAME rule-table pattern tensor-parallel
serving binds (``models/transformer.py param_specs``): a MESH-FREE
table of partition rules, here as ``(regex, dim)`` pairs over tree
paths (the SNIPPETS.md [3] ``match_partition_rules`` idiom) composed
ONTO the megatron specs — ``dp`` lands on a dim the tp rule leaves
free, so FSDP composes with tensor parallelism instead of fighting it
(the same composition argument as ``mesh.zero1_sharding``).

Entry points
------------
``fsdp_rules()``             the checked-in regex rule table
``match_partition_rules``    SNIPPETS [3]: rules × param paths → dim
``fsdp_param_specs``         mesh-free PartitionSpec tree for a cfg
``fsdp_param_shardings``     the specs bound to a mesh
``shard_bytes``              actual per-device bytes from
                             ``addressable_shards`` (the PR-9 ÷tp
                             assertion protocol, here for ÷dp)

``models/transformer.py make_train_step(fsdp=True)`` consumes these;
``tools/analysis/graphlint.py`` verifies the step's DECLARED specs
against its own shape-aware derivation (docs/sharding_readiness.md).
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..base import MXNetError

__all__ = ["fsdp_rules", "match_partition_rules", "fsdp_param_specs",
           "fsdp_param_shardings", "shard_bytes"]


def fsdp_rules() -> List[Tuple[str, int]]:
    """The mesh-free FSDP rule table: ``(path regex, dim)`` — the dim
    of each matching param that shards over ``dp``.

    Dims are chosen to COMPOSE with the megatron tp entries
    (``models/transformer.py param_specs``): where tp shards dim 1
    (wq/wk/wv/w1 and the embedding tables), dp takes dim 0; where tp
    shards dim 0 (wo/w2), dp takes dim 1.  ``type_emb`` is the one
    table whose dim 0 (type_vocab_size=2) cannot divide any real dp
    degree, so its rule names dim 1 — the shape-aware derivation in
    graphlint's audit independently reaches the same choice.  First
    match wins, and an unmatched param is an ERROR, not a silent
    replicate (the SNIPPETS [3] contract): a new param family must be
    added to the table deliberately."""
    return [
        (r"(^|/)type_emb$", 1),
        (r"(^|/)(tok_emb|pos_emb|mlm_dense)$", 0),
        (r"(^|/)(wq|wk|wv|w1)$", 0),
        (r"(^|/)(wo|w2)$", 1),
        (r"(^|/)(bq|bk|bv|bo|b1|b2|mlm_bias)$", 0),
        (r"(^|/)(ln1|ln2|emb_ln|mlm_ln)/(g|b)$", 0),
    ]


def _tree_paths(tree):
    """``(path-string, leaf)`` pairs with ``a/b[3]/c``-style paths —
    the ``named_tree_map(sep='/')`` spelling of SNIPPETS [3]."""
    import jax

    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                if parts:
                    parts[-1] += "[%d]" % p.idx
                else:
                    parts.append("[%d]" % p.idx)
            else:
                parts.append(str(p))
        out.append(("/".join(parts), leaf))
    return out


def match_partition_rules(rules, tree) -> List[Tuple[str, Any, int]]:
    """Apply the rule table to every leaf of ``tree`` (params or
    abstract shapes): returns ``(path, leaf, dim)`` triples.  A leaf
    no rule matches raises — the SNIPPETS [3] contract (silently
    replicating a new 100M-row embedding is how FSDP quietly stops
    being FSDP)."""
    out = []
    for path, leaf in _tree_paths(tree):
        for rx, dim in rules:
            if re.search(rx, path) is not None:
                out.append((path, leaf, dim))
                break
        else:
            raise MXNetError(
                "fsdp: no partition rule matches param %r — add it to "
                "parallel/fsdp.py fsdp_rules()" % path)
    return out


def _compose(spec, dim, axis, ndim):
    """Insert ``axis`` at ``dim`` of ``spec`` (a PartitionSpec or
    None), stacking onto an existing entry as a sub-axis tuple (the
    megatron axis stays outermost: tp partitions the dim first, dp
    subdivides each tp shard)."""
    from jax.sharding import PartitionSpec as P

    entries = list(spec) if spec is not None else []
    entries = entries[:ndim] + [None] * (ndim - len(entries))
    cur = entries[dim]
    if cur is None:
        entries[dim] = axis
    elif isinstance(cur, tuple):
        entries[dim] = cur + (axis,)
    else:
        entries[dim] = (cur, axis)
    return P(*entries)


def _fitting_dim(spec, shape, dim, axis, sizes):
    """The dim of a ``shape``-d param that ``axis`` can shard under
    mesh axis ``sizes``: the rule table's ``dim`` if its extent divides
    by everything that would partition it, else the largest other dim
    that does, else None (the leaf stays replicated over ``axis``).
    BERT-base's vocabulary, 30522 = 2·3·5087, is the case: at dp=4
    ``tok_emb`` shards its hidden dim instead and the 30522-long
    ``mlm_bias`` replicates (first four-chip run, PR 21)."""
    entries = list(spec) if spec is not None else []
    entries = entries[:len(shape)] + [None] * (len(shape) - len(entries))

    def fits(d):
        cur = entries[d]
        axes = () if cur is None else \
            cur if isinstance(cur, tuple) else (cur,)
        parts = sizes[axis]
        for a in axes:
            parts *= sizes[a]
        return shape[d] % parts == 0

    others = sorted((d for d in range(len(shape)) if d != dim),
                    key=lambda d: -shape[d])
    return next((d for d in [dim] + others if fits(d)), None)


def fsdp_param_specs(cfg, dp: str = "dp", tp: Optional[str] = None,
                     sizes: Optional[Dict[str, int]] = None):
    """Mesh-free FSDP ``PartitionSpec`` pytree for a transformer
    config: the megatron table (``param_specs`` — the SAME table
    tensor-parallel serving binds) with ``dp`` composed onto the dim
    the rule table names.  ``tp=None`` drops the tensor axis (a pure
    dp mesh).  With ``sizes`` (mesh axis name -> size, what
    ``fsdp_param_shardings`` passes) the choice is shape-aware: a
    param whose named dim does not divide moves ``dp`` to a dim that
    does, or stays replicated (``_fitting_dim``)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from ..models import transformer as T

    if getattr(cfg, "n_experts", 0):
        raise MXNetError(
            "fsdp: MoE configs are unsupported — the expert dim is "
            "already the 'ep' data-movement axis and the rule table "
            "deliberately does not cover expert weights (compose ep "
            "with ZeRO-1 via shard_optimizer=True instead)")
    base = T.param_specs(cfg, tp=tp)
    shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    triples = {path: dim for path, _, dim
               in match_partition_rules(fsdp_rules(), shapes)}
    leaves, treedef = jax.tree_util.tree_flatten(
        base, is_leaf=lambda x: isinstance(x, P))
    paths = [p for p, _ in _tree_paths(shapes)]
    shape_leaves = [l for _, l in _tree_paths(shapes)]
    assert len(paths) == len(leaves)
    out = []
    for path, leaf, spec in zip(paths, shape_leaves, leaves):
        dim = triples[path]
        if sizes is not None:
            dim = _fitting_dim(spec, leaf.shape, dim, dp, sizes)
        out.append(spec if dim is None
                   else _compose(spec, dim, dp, len(leaf.shape)))
    return jax.tree_util.tree_unflatten(treedef, out)


def fsdp_param_shardings(cfg, mesh, dp: str = "dp"):
    """``fsdp_param_specs`` bound to ``mesh`` (tp included when the
    mesh has a live tp axis, the ``param_shardings`` convention)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from .mesh import live_axis

    if live_axis(mesh, dp) is None:
        raise MXNetError(
            "fsdp needs a live %r mesh axis (size > 1); mesh has %s"
            % (dp, dict(mesh.shape)))
    specs = fsdp_param_specs(cfg, dp=dp, tp=live_axis(mesh, "tp"),
                             sizes=dict(mesh.shape))
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def shard_bytes(tree, device=None) -> Tuple[int, int]:
    """(total_bytes, per_device_bytes) of a pytree of live arrays,
    per-device measured from the ACTUAL ``addressable_shards`` on
    ``device`` (default: the first device seen) — the PR-9 protocol:
    the ÷dp claim is asserted against what the runtime placed, not
    against the specs."""
    import jax

    total = 0
    per_dev = 0
    dev = device
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        total += leaf.nbytes
        shards = leaf.addressable_shards
        if dev is None:
            dev = shards[0].device
        for sh in shards:
            if sh.device == dev:
                per_dev += sh.data.nbytes
    return total, per_dev
