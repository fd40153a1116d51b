"""graphlint (mxlint analyzer 5) — jaxpr-level audit of the repo's hot
compiled programs.

Analyzers 1–4 check *source*; nothing checked the *compiled programs*
the perf story rides on.  Donation of the paged KV pools, bf16/int8
dtype discipline in the attention paths, and per-program HBM footprints
were enforced only by convention — one refactor that silently drops
``donate_argnums`` doubles serving HBM and no test notices.  graphlint
closes that hole: a **registry** of the repo's hot compiled programs
(:func:`live_programs` — serving step in both kernels, the COW page
copy, GPT ``generate`` and the speculative block, the transformer /
GPT train steps, the Pallas paged-attention wrapper) is traced via
``jax.make_jaxpr`` / ``jax.eval_shape`` on checked-in abstract shapes
(tiny configs, declared right next to each builder — no weights ever
materialize, no program ever compiles or runs), and jaxpr-walk rules
audit the result.

Rules
-----
``graph-donation``  Every arg a :class:`ProgramSpec` declares donated
    must actually be donated AND be in-place-updatable: the lowering
    must carry ``tf.aliasing_output`` on each of its flattened leaves
    (jax only aliases a donated buffer that is shape/dtype-matched to
    an output).  A refactor that drops ``donate_argnums`` — or breaks
    the output match so donation silently stops applying — is a
    finding.

``graph-hbm-budget``  Peak live bytes from a linear-scan live-range
    estimator over the jaxpr (:func:`peak_live_bytes`: inputs live
    from entry to last use, each equation allocates its outputs, a
    value dies after its last consumer; nested jaxprs — pjit / scan /
    while / cond / remat — contribute their own internal peak at their
    program point; ``pallas_call`` bodies are VMEM scratch and are not
    recursed into).  The estimate is compared against the committed
    manifest ``tools/analysis/hbm_budgets.json``: exceeding a
    program's ``budget_bytes``, or growing >10% over its recorded
    ``peak_bytes``, is a finding.  ``--update-budgets`` re-records
    measurements but NEVER relaxes a budget (the perf-gate semantics:
    widening takes a hand edit with justification in review).  The
    numbers are estimates on the registry's tiny abstract shapes — a
    trajectory gate, not a chip measurement.

``graph-dtype-drift``  In a program whose ``dtype_region`` is declared
    (the bf16-compute / int8-KV serving and decode programs), every
    ``convert_element_type`` from bf16/int8 **to f32** must land on a
    declared accumulation point: ``f32_allow`` maps allowed last-dim
    sizes to labels (layer-norm statistics over ``d_model``, the
    f32 logits over ``vocab``, the KV-quantization accumulation over
    ``head_dim``, softmax statistics over the sequence dim).  An
    undeclared upcast — e.g. a refactor that casts the KV pool or a
    gathered page view to f32, materializing a double-width copy every
    step — is a finding, anchored at the offending source line.
    Scalar (rank-0) converts are always allowed; downcasts are not
    policed (they are the intended compute direction).  Known
    boundary: the allowance is a last-dim filter, so an upcast that
    SHARES an accumulation point's last dim — e.g. an f32 copy of the
    (T, d_model) residual stream, indistinguishable by aval from the
    layer-norm statistics upcast and feeding the same mixed consumer
    sets — passes; the rule's target class is the KV/pool/page-view
    upcasts, whose last dims (2·dh, 2, page dims) are distinct from
    every declared point.

``graph-host-sync``  Hot programs must stay host-free: any callback /
    infeed / outfeed / debug-print primitive in the jaxpr (at any
    nesting depth) is a finding — a host round-trip inside the serving
    step or a train step serializes the device on the host every
    iteration.

``graph-sharding-readiness``  (round 14, tensor-parallel serving) The
    engine's DECLARED step-program shardings (``serving/engine.py
    step_input_specs`` — what ``ServingEngine(tp=N)`` lowers through)
    must cover every input: params matching the megatron rules
    (``models/transformer.py param_specs``; int8 ``{"q","s"}`` leaves
    verified against graphlint's own independent derivation of the
    float rule), pools sharding exactly the heads axis over ``tp``,
    host-built rows replicated.  UNCOVERED count must be 0 and covered
    rows must MATCH — a drifted declaration (silent per-step reshard /
    gather) or a new unsharded input fails tier-1.
    :func:`sharding_audit_md` renders the same table into the
    checked-in ``docs/sharding_readiness.md`` (pre-round-14 this was
    the report-mode ROADMAP-1 work-list; now it is a verified
    contract).  The sharded step's registry entry
    (``serving_step_tp``) additionally records per-device (÷tp)
    expected peaks next to its ``hbm_budgets.json`` row.

Scope / suppression: findings go through the shared pragma + baseline
machinery (``findings.py``).  ``--changed-only`` re-traces a program
only when a file in its *recorded trace closure* (the source files its
jaxpr's tracebacks touched on the last ``--update-budgets``, stored in
the manifest) changed; ``--all``, ``--write-baseline`` and
``--update-budgets`` always trace everything.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import re
import sys
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from .findings import Finding, apply_pragmas

__all__ = ["ProgramSpec", "spec", "live_programs", "peak_live_bytes",
           "check_program", "run", "update_budgets", "load_budgets",
           "sharding_audit_md", "BUDGETS_PATH", "AUDIT_PATH",
           "GROWTH", "HEADROOM"]

BUDGETS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "hbm_budgets.json")
AUDIT_PATH = "docs/sharding_readiness.md"

# graphlint audits the IMPORTED mxnet_tpu checkout — the one this file
# lives in — whatever --root the caller passes (imports do not follow
# root).  Trace closures are always resolved against this root so a
# foreign --root cannot wipe the recorded closures; runner.main()
# rejects foreign roots for the graphlint write modes outright.
OWN_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GROWTH = 0.10       # >10% live-bytes growth vs the manifest = finding
HEADROOM = 1.15     # initial budget = ceil(peak * HEADROOM)

# kernel bodies are VMEM-scratch programs (their f32 online-softmax
# accumulators are the declared-by-design accumulation points) — never
# recursed into by any rule
_SKIP_SUBJAXPR = {"pallas_call"}

_CALLBACK_RE = re.compile(r"callback|infeed|outfeed|debug_print")


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One registered hot program.

    ``build()`` returns ``(fn, args)``: ``fn`` the LIVE callable from
    the repo module (so a refactor there is what gets audited) and
    ``args`` a tuple of abstract ``ShapeDtypeStruct`` pytrees — the
    checked-in shapes.  ``donate`` lists the positional args the repo
    declares donated (``fn`` must be jitted for the check to run).
    ``dtype_region`` ("bf16"/"int8") turns on drift checking with the
    ``f32_allow`` {last_dim: label} accumulation points.  ``hot``
    enforces host-sync-freedom.  ``path``/``line`` anchor registry-
    level findings (captured at :func:`spec` call sites)."""
    name: str
    build: Callable[[], Tuple[Any, tuple]]
    donate: Tuple[int, ...] = ()
    dtype_region: Optional[str] = None
    f32_allow: Any = None          # {last_dim: label}
    hot: bool = True
    path: str = ""
    line: int = 0
    # files that shape the program WITHOUT leaving traceback frames in
    # the jaxpr (e.g. sharding-spec construction at jit time — the FSDP
    # rule table); merged into the recorded closure so --changed-only
    # re-traces on their edits too (round 19)
    extra_closure: Tuple[str, ...] = ()


def spec(name, build, *, donate=(), dtype_region=None, f32_allow=None,
         hot=True, extra_closure=()):
    """Register a program, anchoring findings at the caller's line."""
    frame = sys._getframe(1)
    return ProgramSpec(name=name, build=build, donate=tuple(donate),
                       dtype_region=dtype_region,
                       f32_allow=dict(f32_allow or {}), hot=hot,
                       path=frame.f_code.co_filename,
                       line=frame.f_lineno,
                       extra_closure=tuple(extra_closure))


# ---------------------------------------------------------------------------
# the live registry — the repo's hot compiled programs, on the
# checked-in abstract shapes below (tiny configs: tracing is abstract,
# nothing allocates or compiles)
# ---------------------------------------------------------------------------

# serving-step registry shapes (the paper's serving config: bf16
# compute, weight-only-int8 params, int8-KV pages, one draft row)
_SLOTS, _PAGE, _CHUNK, _SPEC_K = 2, 4, 4, 1
_GEN_B, _GEN_P, _GEN_NEW = 1, 8, 8
# tensor-parallel serving step (round 14): tp degree of the sharded
# registry entry, and the ÷tp columns of the per-device expected-peak
# manifest rows (both must divide gpt_tiny's 4 heads)
_TP = 2
_PER_DEVICE_TPS = (2, 4)
# FSDP BERT train step (round 19): dp degree of the sharded train
# registry entries, and the dp size the train-audit's shape-aware
# derivation divides against (8 = the virtual tier-1 mesh; it must
# exceed bert_tiny's type_vocab_size=2 so the derivation is forced off
# type_emb's dim 0, the case the regex table also special-cases)
_TRAIN_DP = 2
_AUDIT_DP_SIZE = 8


def _gpt_cfg():
    from mxnet_tpu.models import gpt as G
    return G.gpt_tiny(dtype="bfloat16")


def _serve_geometry(cfg):
    pps = -(-cfg.max_len // _PAGE)
    n_rows = _SLOTS * (1 + _SPEC_K) + _CHUNK
    num_pages = _SLOTS * pps + 1
    return pps, n_rows, num_pages


def _abstract_pools(cfg, num_pages):
    import jax
    import jax.numpy as jnp
    H = cfg.n_heads
    dh = cfg.d_model // H
    return [{"kv": jax.ShapeDtypeStruct((num_pages, _PAGE, H, 2 * dh),
                                        jnp.int8),
             # round-22 tile-shaped scale planes (serving/paged_kv.py)
             "s": jax.ShapeDtypeStruct((num_pages, 2, _PAGE, H),
                                       jnp.float32)}
            for _ in range(cfg.n_layers)]


def _abstract_qparams(cfg):
    import jax
    from mxnet_tpu.models import gpt as G
    return jax.eval_shape(lambda: G.quantize_decode_params(
        G.init_params(jax.random.PRNGKey(0), cfg)))


def _serving_step_args(cfg):
    import jax
    import jax.numpy as jnp
    pps, n_rows, num_pages = _serve_geometry(cfg)
    i32 = jnp.int32
    return (_abstract_qparams(cfg), _abstract_pools(cfg, num_pages),
            jax.ShapeDtypeStruct((n_rows,), i32),
            jax.ShapeDtypeStruct((n_rows,), i32),
            jax.ShapeDtypeStruct((n_rows,), i32),
            jax.ShapeDtypeStruct((n_rows,), jnp.bool_),
            jax.ShapeDtypeStruct((_SLOTS + 1, pps), i32),
            jax.ShapeDtypeStruct((_SLOTS, 1 + _SPEC_K), i32))


def _build_serving_step(kernel):
    from mxnet_tpu.serving.engine import _make_step
    cfg = _gpt_cfg()
    pps, n_rows, _ = _serve_geometry(cfg)
    fn = _make_step(cfg, _SLOTS, n_rows, pps, _PAGE, True,
                    kernel=kernel, n_sample=1 + _SPEC_K)
    return fn, _serving_step_args(cfg)


def build_serving_step_xla():
    return _build_serving_step("xla")


def build_serving_step_pallas():
    return _build_serving_step("pallas")


def build_serving_step_overlap():
    """The latency-hiding step variant (round 21): the SAME live
    ``_make_step`` builder with ``overlap=True`` — two extra inputs
    (the previous step's device-resident ``(S, n_sample)`` argmax
    matrix and the per-row ``tok_src`` selector) and one gather +
    ``where`` at the top of the graph.  Donation of the pools must
    survive the wrapper (the overlap engine runs EVERY step through
    this program, fenced steps included), and its peak is gated
    against its own manifest row — the selector must cost rows, not
    a second resident pool."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.serving.engine import _make_step
    cfg = _gpt_cfg()
    pps, n_rows, _ = _serve_geometry(cfg)
    fn = _make_step(cfg, _SLOTS, n_rows, pps, _PAGE, True,
                    kernel="xla", n_sample=1 + _SPEC_K, overlap=True)
    args = _serving_step_args(cfg) + (
        jax.ShapeDtypeStruct((_SLOTS, 1 + _SPEC_K), jnp.int32),
        jax.ShapeDtypeStruct((n_rows,), jnp.int32))
    return fn, args


def _registry_mesh():
    """The tp mesh the sharded registry entry traces over — the same
    virtual CPU mesh the tier-1 conftest and the MULTICHIP dry-runs
    force (the CLI entry, ``tools/analysis/__main__.py``, requests it
    before jax's backend initializes; library imports deliberately do
    not mutate topology)."""
    import jax
    from mxnet_tpu.parallel.mesh import serving_mesh
    if len(jax.devices()) < _TP:
        raise RuntimeError(
            "graphlint: the serving_step_tp registry entry needs a "
            "%d-device mesh but only %d device(s) are visible — jax "
            "initialized before tools.analysis could request the "
            "virtual CPU mesh; run via `python -m tools.analysis` or "
            "call jax.config.update('jax_num_cpu_devices', 8) first"
            % (_TP, len(jax.devices())))
    return serving_mesh(_TP)


def build_serving_step_tp():
    """The tensor-parallel serving step: the SAME live ``_make_step``
    builder, lowered through a tp=``_TP`` mesh with the engine's
    declared shardings (megatron params, heads-sharded pools,
    replicated host rows).  Donation of the sharded pools must survive
    the lowering — the ``graph-donation`` gate runs on this entry like
    any other."""
    from mxnet_tpu.serving.engine import _make_step
    cfg = _gpt_cfg()
    pps, n_rows, _ = _serve_geometry(cfg)
    args = _serving_step_args(cfg)
    fn = _make_step(cfg, _SLOTS, n_rows, pps, _PAGE, True,
                    kernel="xla", n_sample=1 + _SPEC_K,
                    mesh=_registry_mesh(), params=args[0])
    return fn, args


def build_serving_step_pallas_tp():
    """Round 22: the PALLAS serving step lowered through the tp mesh
    — ``paged_attention`` shard_map'ed so each device walks its 1/tp
    heads slice of the heads-sharded pool (attention collective-free
    per head; the wo psum stays outside the kernel).  Donation of the
    sharded pools must survive BOTH the shard_map and the pallas_call
    inside it, and the per-device peak divides like the XLA tp
    entry's."""
    from mxnet_tpu.serving.engine import _make_step
    cfg = _gpt_cfg()
    pps, n_rows, _ = _serve_geometry(cfg)
    args = _serving_step_args(cfg)
    fn = _make_step(cfg, _SLOTS, n_rows, pps, _PAGE, True,
                    kernel="pallas", n_sample=1 + _SPEC_K,
                    mesh=_registry_mesh(), params=args[0])
    return fn, args


def build_serving_page_install_put():
    """The put-transport install (round 22): page content that
    arrived as zero-copy ``/dev/shm`` views rides a ``device_put``
    into the SAME donated whole-page scatter the socket path runs —
    one program for both transports is the bit-identity argument.
    Registered separately so the zero-copy path's donation is gated
    on its own: a regression that copies the pools here would erase
    exactly the bytes the put saved."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.serving.paged_kv import _make_install
    cfg = _gpt_cfg()
    _, _, num_pages = _serve_geometry(cfg)
    H = cfg.n_heads
    dh = cfg.d_model // H
    b = 4
    base = _make_install(cfg, True, b)
    fn = jax.jit(
        lambda pools, ids, content: base(
            pools, ids, jax.tree_util.tree_map(jnp.asarray, content)),
        donate_argnums=(0,))
    content = [{"kv": jax.ShapeDtypeStruct((b, _PAGE, H, 2 * dh),
                                           jnp.int8),
                "s": jax.ShapeDtypeStruct((b, 2, _PAGE, H),
                                          jnp.float32)}
               for _ in range(cfg.n_layers)]
    return fn, (_abstract_pools(cfg, num_pages),
                jax.ShapeDtypeStruct((b,), jnp.int32), content)


def build_serving_page_install():
    """The disaggregated page-install scatter (round 15): received
    page content lands in the donated pools in place — same
    in-place-update contract as the step program, so its donation and
    HBM peak are gated like the step's (``serving/paged_kv.py
    _make_install``; bucket 4 pages, int8-KV layout)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.serving.paged_kv import _make_install
    cfg = _gpt_cfg()
    _, _, num_pages = _serve_geometry(cfg)
    H = cfg.n_heads
    dh = cfg.d_model // H
    b = 4
    fn = _make_install(cfg, True, b)
    content = [{"kv": jax.ShapeDtypeStruct((b, _PAGE, H, 2 * dh),
                                           jnp.int8),
                "s": jax.ShapeDtypeStruct((b, 2, _PAGE, H),
                                          jnp.float32)}
               for _ in range(cfg.n_layers)]
    return fn, (_abstract_pools(cfg, num_pages),
                jax.ShapeDtypeStruct((b,), jnp.int32), content)


def build_tier_page_restore():
    """The KV-tiering single-page install (round 18): a host-tier
    spill/restore/swap moves pages one (or a small power-of-two run)
    at a time through the SAME donated scatter family as the
    round-15 transfer path, but at bucket 1 — the shape every
    pressure spill's restore and every swap-in resume compiles.  Its
    donation must alias the pools in place (a copy here would double
    the pool bytes at every preemption resume) and its peak is
    budget-gated like the step's."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.serving.paged_kv import _make_install
    cfg = _gpt_cfg()
    _, _, num_pages = _serve_geometry(cfg)
    H = cfg.n_heads
    dh = cfg.d_model // H
    b = 1
    fn = _make_install(cfg, True, b)
    content = [{"kv": jax.ShapeDtypeStruct((b, _PAGE, H, 2 * dh),
                                           jnp.int8),
                "s": jax.ShapeDtypeStruct((b, 2, _PAGE, H),
                                          jnp.float32)}
               for _ in range(cfg.n_layers)]
    return fn, (_abstract_pools(cfg, num_pages),
                jax.ShapeDtypeStruct((b,), jnp.int32), content)


def build_cow_page_copy():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.serving.engine import _make_copy
    cfg = _gpt_cfg()
    _, _, num_pages = _serve_geometry(cfg)
    fn = _make_copy(cfg, True)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return fn, (_abstract_pools(cfg, num_pages), scalar, scalar)


def build_gpt_generate():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import gpt as G
    cfg = _gpt_cfg()
    params = jax.eval_shape(
        lambda: G.init_params(jax.random.PRNGKey(0), cfg))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def gen(params, prompt, rng):
        return G.generate(params, cfg, prompt, _GEN_NEW, rng=rng,
                          kv_int8=True)
    return gen, (params,
                 jax.ShapeDtypeStruct((_GEN_B, _GEN_P), jnp.int32), key)


def build_gpt_spec_block():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import gpt as G
    cfg = _gpt_cfg()
    params = jax.eval_shape(
        lambda: G.init_params(jax.random.PRNGKey(0), cfg))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def gen(params, prompt, rng):
        return G.generate_speculative(params, cfg, prompt, _GEN_NEW,
                                      K=2, rng=rng, kv_int8=True)
    return gen, (params,
                 jax.ShapeDtypeStruct((_GEN_B, _GEN_P), jnp.int32), key)


def _train_batch(with_labels):
    import jax
    import jax.numpy as jnp
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    if with_labels:
        batch["labels"] = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    return batch


def _train_mesh():
    """The dp mesh the FSDP train registry entries lower through —
    same virtual-CPU-mesh contract as :func:`_registry_mesh`."""
    import jax
    from mxnet_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < _TRAIN_DP:
        raise RuntimeError(
            "graphlint: the bert_train_step_fsdp registry entries need "
            "a %d-device mesh but only %d device(s) are visible — run "
            "via `python -m tools.analysis` or call "
            "jax.config.update('jax_num_cpu_devices', 8) first"
            % (_TRAIN_DP, len(jax.devices())))
    return make_mesh({"dp": _TRAIN_DP},
                     devices=list(jax.devices())[:_TRAIN_DP])


def _bert_fsdp_cfg(param_dtype):
    from mxnet_tpu.models import transformer as T
    return T.bert_tiny(use_flash=False, remat=False, dropout=0.0,
                       dtype=("float32" if param_dtype == "float32"
                              else "bfloat16"),
                       param_dtype=param_dtype)


def _build_bert_train_fsdp(param_dtype):
    """The FSDP BERT pretrain step (round 19, ROADMAP 5): the live
    ``make_train_step(fsdp=True)`` builder lowered through a
    dp=``_TRAIN_DP`` mesh with params + optimizer moments sharded by
    the ``parallel/fsdp.py`` rule table.  Donation of the (params,
    opt_state) tuple must survive the sharded lowering — the state is
    updated in place every step, and a dropped donation doubles
    resident training HBM exactly like the serving-pool case.  The
    abstract state is built from the same ``init_params`` /
    ``optax.adamw().init`` pair the live ``init_state`` materializes
    (eval_shape only; the adamw state STRUCTURE does not depend on
    hyperparameters)."""
    import jax
    import optax
    from mxnet_tpu.models import transformer as T
    cfg = _bert_fsdp_cfg(param_dtype)
    _, step = T.make_train_step(cfg, mesh=_train_mesh(), fsdp=True)
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(optax.adamw(1e-4).init, params)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return step, ((params, opt), _train_batch(True), key)


def build_bert_train_step_fsdp():
    return _build_bert_train_fsdp("float32")


def build_bert_train_step_fsdp_bf16():
    return _build_bert_train_fsdp("bfloat16")


def build_bert_train_step_fsdp_bucketed():
    """The bucketed-overlap FSDP step (round 21): the live
    ``make_train_step(fsdp=True, bucket_overlap=True)`` — backward
    runs as a manual ``lax.scan`` over layers with each layer's
    reduce-scatter carried INSIDE the scan body, so the collective
    overlaps the next layer's grad math instead of fusing into one
    tail allreduce.  Donation of (params, opt_state) must survive the
    scan-carried lowering, and its peak is gated against its own
    manifest row — the scan carry must not duplicate the grad
    accumulator."""
    import jax
    import optax
    from mxnet_tpu.models import transformer as T
    cfg = _bert_fsdp_cfg("float32")
    _, step = T.make_train_step(cfg, mesh=_train_mesh(), fsdp=True,
                                bucket_overlap=True)
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(optax.adamw(1e-4).init, params)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return step, ((params, opt), _train_batch(True), key)


def build_transformer_train_step():
    import jax
    from mxnet_tpu.models import transformer as T
    init_state, step = T.make_train_step(T.bert_tiny())
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = jax.eval_shape(init_state, key)
    return step, (state, _train_batch(True), key)


def build_gpt_train_step():
    import jax
    from mxnet_tpu.models import gpt as G
    init_state, step = G.make_train_step(G.gpt_tiny())
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = jax.eval_shape(init_state, key)
    return step, (state, _train_batch(False), key)


def build_serving_step_latent():
    """The step of a latent-attention, routed-experts family
    (``models/deepseek_v3.py``, PR 32) as a TPU engine runs it: the
    SAME live ``_make_step`` builder, pipelined, latent pages donated,
    the expert layers' counts behind the tokens.  One dense and one
    expert layer at toy widths, 8 routed experts of which 4 are held."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import deepseek_v3 as M
    from mxnet_tpu.serving.engine import _make_step
    from mxnet_tpu.serving.paged_kv import latent_width
    cfg = M.DeepseekV3Config(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, d_ff=128, moe_d_ff=32, n_routed_experts=8,
        n_shared_experts=1, top_k=2, n_group=2, topk_group=1,
        first_k_dense=1, held_count=4, rope_factor=4.0,
        rope_mscale_all_dim=1.0, dtype="bfloat16")
    pps, n_rows = 4, _SLOTS + _CHUNK
    fn = _make_step(cfg, _SLOTS, n_rows, pps, _PAGE, False, kernel="xla",
                    overlap=True)
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    params = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    pools = [{"kv": sds((_SLOTS * pps + 1, _PAGE,
                         latent_width(*cfg.latent_row)), jnp.bfloat16)}
             for _ in range(cfg.n_layers)]
    n_counts = len(M.STEP_COUNTERS)
    return fn, (params, pools, sds((n_rows,), i32), sds((n_rows,), i32),
                sds((n_rows,), i32), sds((n_rows,), jnp.bool_),
                sds((_SLOTS + 1, pps), i32), sds((_SLOTS, 1), i32),
                sds((_SLOTS + n_counts, 1), i32), sds((n_rows,), i32))


def build_serving_step_layer_kinds():
    """The step of a family whose layers keep different things
    (``models/lfm2_moe.py``, PR 36) as a TPU engine runs it: the SAME
    live ``_make_step`` builder, pipelined; the convolution layers'
    windows and the attention layer's pages donated, each in the layer
    that keeps it; the expert layers' three counts behind the tokens.
    conv, conv, attention, conv at toy widths, 1 dense and 3 expert
    layers of 8 experts."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import lfm2_moe as M
    from mxnet_tpu.serving.engine import _make_step
    from mxnet_tpu.serving.paged_kv import PagedKVCache
    cfg = M.Lfm2MoeConfig(
        vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, moe_d_ff=32, n_experts=8, top_k=2,
        n_dense_layers=1,
        layer_types=("conv", "conv", "full_attention", "conv"),
        dtype="bfloat16")
    pps, n_rows = 4, _SLOTS + _CHUNK
    fn = _make_step(cfg, _SLOTS, n_rows, pps, _PAGE, False, kernel="xla",
                    overlap=True)
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    params = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    pools = jax.eval_shape(lambda: PagedKVCache(
        cfg, _SLOTS * pps + 1, _PAGE, num_slots=_SLOTS).pools)
    n_counts = len(M.STEP_COUNTERS)
    return fn, (params, pools, sds((n_rows,), i32), sds((n_rows,), i32),
                sds((n_rows,), i32), sds((n_rows,), jnp.bool_),
                sds((_SLOTS + 1, pps), i32), sds((_SLOTS, 1), i32),
                sds((_SLOTS + 1,), jnp.bool_),
                sds((_SLOTS + n_counts, 1), i32), sds((n_rows,), i32))


def build_serving_step_sliding():
    """The step of a family whose sliding-window layers keep a ring of
    their last positions per slot beside full layers that keep pages
    (``models/exaone_moe.py``) as a TPU engine runs it: the SAME
    live ``_make_step`` builder, pipelined; the rings and the pages
    donated, each in the layer that keeps it; the expert layers' and the
    rings' counts behind the tokens.  sliding, sliding, full, sliding at
    toy widths and a window of 8, 1 dense and 3 expert layers holding 2
    of 8 experts and a shared one."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import exaone_moe as M
    from mxnet_tpu.serving.engine import _make_step
    from mxnet_tpu.serving.paged_kv import PagedKVCache
    cfg = M.ExaoneMoeConfig(
        vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, moe_d_ff=32, n_routed_experts=8,
        n_shared_experts=1, top_k=2, sliding_windows=(8, 8, 0, 8),
        mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
        held_count=2, dtype="bfloat16")
    pps, n_rows = 4, _SLOTS + _CHUNK
    fn = _make_step(cfg, _SLOTS, n_rows, pps, _PAGE, False, kernel="xla",
                    overlap=True)
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    params = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    pools = jax.eval_shape(lambda: PagedKVCache(
        cfg, _SLOTS * pps + 1, _PAGE, num_slots=_SLOTS).pools)
    n_counts = len(M.STEP_COUNTERS)
    return fn, (params, pools, sds((n_rows,), i32), sds((n_rows,), i32),
                sds((n_rows,), i32), sds((n_rows,), jnp.bool_),
                sds((_SLOTS + 1, pps), i32), sds((_SLOTS, 1), i32),
                sds((_SLOTS + 1,), jnp.bool_),
                sds((_SLOTS + n_counts, 1), i32), sds((n_rows,), i32))


def build_paged_attention_kernel():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels.paged_attention import paged_attention
    cfg = _gpt_cfg()
    H = cfg.n_heads
    dh = cfg.d_model // H
    pps, n_rows, num_pages = _serve_geometry(cfg)

    def attend(q, kv, s, bt, pos):
        return paged_attention(q, kv, s, bt, pos, page_size=_PAGE)
    fn = jax.jit(attend)
    return fn, (jax.ShapeDtypeStruct((n_rows, H, dh), jnp.bfloat16),
                jax.ShapeDtypeStruct((num_pages, _PAGE, H, 2 * dh),
                                     jnp.int8),
                jax.ShapeDtypeStruct((num_pages, _PAGE, H, 2),
                                     jnp.float32),
                jax.ShapeDtypeStruct((n_rows, pps), jnp.int32),
                jax.ShapeDtypeStruct((n_rows,), jnp.int32))


def live_programs() -> List[ProgramSpec]:
    """The audited registry.  Declared accumulation points
    (``f32_allow`` last dims, gpt_tiny geometry): 64 = ``d_model``
    (layer-norm statistics), 1024 = ``vocab`` (f32 logits), 16 =
    ``head_dim`` (KV-quantization accumulation — ``models/gpt.py
    _kv_quantize`` upcasts k/v once and computes scale + grid in f32),
    8 = the prompt/sequence dim (softmax statistics on the prefill's
    jnp attention reference)."""
    cfg = _gpt_cfg()
    dh = cfg.d_model // cfg.n_heads
    acc = {cfg.d_model: "ln-stats", cfg.vocab_size: "logits",
           dh: "quant-acc"}
    gen_acc = dict(acc)
    gen_acc[_GEN_P] = "softmax-stats"
    return [
        spec("serving_step", build_serving_step_xla, donate=(1,),
             dtype_region="int8", f32_allow=acc),
        spec("serving_step_pallas", build_serving_step_pallas,
             donate=(1,), dtype_region="int8", f32_allow=acc),
        # round 21: the overlap (tok_src) step variant — every step
        # of an overlap engine runs through it, so its donation and
        # budget are gated exactly like the serial program's
        spec("serving_step_overlap", build_serving_step_overlap,
             donate=(1,), dtype_region="int8", f32_allow=acc),
        # PR 32: a latent-attention family's step (no dtype region:
        # its norms, router and softmax are float32 by design, at
        # widths of its own)
        spec("serving_step_latent", build_serving_step_latent,
             donate=(1,)),
        # PR 36: a family whose layers keep different things (windows
        # beside pages): every leaf of the mixed pools donated
        spec("serving_step_layer_kinds", build_serving_step_layer_kinds,
             donate=(1,)),
        # sliding-window rings beside pages, every leaf donated
        spec("serving_step_sliding", build_serving_step_sliding,
             donate=(1,)),
        spec("serving_step_tp", build_serving_step_tp, donate=(1,),
             dtype_region="int8", f32_allow=acc),
        # round 22: the mesh-lowered PALLAS step — the chip-ready
        # data path; donation through shard_map + pallas_call gated
        # like the XLA tp entry, per-device peak recorded ÷tp
        spec("serving_step_pallas_tp2", build_serving_step_pallas_tp,
             donate=(1,), dtype_region="int8", f32_allow=acc,
             extra_closure=("mxnet_tpu/parallel/mesh.py",)),
        spec("cow_page_copy", build_cow_page_copy, donate=(0,),
             dtype_region="int8", f32_allow={}),
        spec("serving_page_install", build_serving_page_install,
             donate=(0,), dtype_region="int8", f32_allow={}),
        # round 22: the same install scatter as the put transport
        # drives it (device_put of mapped segment views)
        spec("serving_page_install_put",
             build_serving_page_install_put,
             donate=(0,), dtype_region="int8", f32_allow={},
             extra_closure=("mxnet_tpu/serving/transport.py",
                            "mxnet_tpu/serving/page_streamer.py")),
        spec("tier_page_restore", build_tier_page_restore,
             donate=(0,), dtype_region="int8", f32_allow={}),
        spec("gpt_generate", build_gpt_generate,
             dtype_region="int8", f32_allow=gen_acc),
        spec("gpt_spec_block", build_gpt_spec_block,
             dtype_region="int8", f32_allow=gen_acc),
        spec("paged_attention_kernel", build_paged_attention_kernel,
             dtype_region="int8", f32_allow={}),
        # train steps deliberately carry no dtype_region: the AMP
        # master-weight pattern (bf16 compute, f32 params/optimizer)
        # upcasts at every param boundary by design
        spec("transformer_train_step", build_transformer_train_step,
             donate=(0,)),
        spec("gpt_train_step", build_gpt_train_step),
        # round 19 (ROADMAP 5): the FSDP BERT pretrain step, lowered
        # through the dp mesh with rule-table-sharded params + moments
        # — donation of (params, opt_state) gated like the serving
        # pools', f32 and bf16-param variants
        spec("bert_train_step_fsdp", build_bert_train_step_fsdp,
             donate=(0,),
             extra_closure=("mxnet_tpu/parallel/fsdp.py",
                            "mxnet_tpu/parallel/mesh.py")),
        spec("bert_train_step_fsdp_bf16",
             build_bert_train_step_fsdp_bf16, donate=(0,),
             extra_closure=("mxnet_tpu/parallel/fsdp.py",
                            "mxnet_tpu/parallel/mesh.py")),
        # round 21: the layer-bucketed reduce-scatter-overlap step —
        # scan-carried collectives; donation gated like the fused one
        spec("bert_train_step_fsdp_bucketed",
             build_bert_train_step_fsdp_bucketed, donate=(0,),
             extra_closure=("mxnet_tpu/parallel/fsdp.py",
                            "mxnet_tpu/parallel/mesh.py")),
    ]


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    """Yield nested (Closed)Jaxprs of an equation — pjit / scan /
    while / cond / remat / custom_* bodies; ``pallas_call`` is
    deliberately opaque (VMEM-scratch kernel internals)."""
    from jax.extend import core
    if eqn.primitive.name in _SKIP_SUBJAXPR:
        return
    for v in eqn.params.values():
        if isinstance(v, core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, core.Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                if isinstance(x, core.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, core.Jaxpr):
                    yield x


def _walk_eqns(jaxpr):
    """Depth-first over every equation at every nesting level."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


def peak_live_bytes(jaxpr) -> int:
    """Linear-scan live-range estimate of a jaxpr's peak live bytes.

    Inputs/consts are live from entry to their last use, each equation
    allocates its outputs, and a value dies after its last consumer
    (program outputs live to the end).  An equation with nested
    jaxprs contributes the nested peak *beyond its own operands* at
    that program point (for ``cond``/``while``/``scan`` that is the
    worst branch / one iteration — per-iteration temporaries do not
    accumulate).  Donation is not modeled: a donated buffer counts on
    both sides of its update for the one equation where old and new
    overlap, which XLA aliases away — a deliberate, deterministic
    overestimate.  The point is the trajectory, not the absolute
    number."""
    from jax.extend import core
    # jax.extend.core does not re-export DropVar
    from jax._src.core import DropVar
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = len(jaxpr.eqns)
    last: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, core.Var):
                last[v] = i
    for v in jaxpr.outvars:
        if isinstance(v, core.Var):
            last[v] = n
    live = 0
    seen: Set[Any] = set()
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        if v not in seen:
            seen.add(v)
            live += _aval_bytes(v.aval)
    peak = live
    for i, eqn in enumerate(jaxpr.eqns):
        inner = 0
        for sub in _sub_jaxprs(eqn):
            operand = sum(_aval_bytes(v.aval) for v in sub.invars)
            inner = max(inner, max(0, peak_live_bytes(sub) - operand))
        alloc = 0
        for v in eqn.outvars:
            if not isinstance(v, DropVar):
                alloc += _aval_bytes(v.aval)
        live += alloc
        peak = max(peak, live + inner)
        freed = 0
        dead: Set[Any] = set()
        for v in eqn.invars:
            if isinstance(v, core.Var) and v not in dead \
                    and last.get(v) == i:
                dead.add(v)
                freed += _aval_bytes(v.aval)
        for v in eqn.outvars:
            if not isinstance(v, DropVar) and v not in last:
                freed += _aval_bytes(v.aval)   # produced, never read
        live -= freed
    return peak


def _repo_frame(eqn, root) -> Optional[Tuple[str, int]]:
    """Innermost traceback frame inside the repo, as (relpath, line)."""
    tb = eqn.source_info.traceback
    if tb is None:
        return None
    root = os.path.abspath(root) + os.sep
    for f in tb.frames:
        name = f.file_name
        if name.startswith(root) and "site-packages" not in name:
            return os.path.relpath(name, root[:-1]), f.line_num
    return None


def _trace_closure(jaxpr, root) -> Set[str]:
    """Repo-relative LIBRARY files the trace touched (the program's
    recorded trace closure, for ``--changed-only`` scoping).  Only
    ``mxnet_tpu/`` files qualify: traceback frames also carry the
    driver stack (the CLI runner, a test file, whatever invoked the
    trace), which would make the closure depend on who ran the update.
    Changes under ``tools/analysis`` always re-trace everything via
    :func:`_needs_trace`, so the infra needs no closure entry."""
    root = OWN_ROOT          # the traced modules live HERE (imports
    root_abs = os.path.abspath(root) + os.sep   # ignore --root)
    files: Set[str] = set()
    for eqn in _walk_eqns(getattr(jaxpr, "jaxpr", jaxpr)):
        tb = eqn.source_info.traceback
        if tb is None:
            continue
        for f in tb.frames:
            name = f.file_name
            if name.startswith(root_abs) and "site-packages" not in name:
                rel = os.path.relpath(name, root)
                if rel.startswith("mxnet_tpu" + os.sep):
                    files.add(rel)
    return files


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------

def _rel(path, root) -> str:
    path = os.path.abspath(path)
    root = os.path.abspath(root)
    try:
        rel = os.path.relpath(path, root)
    except ValueError:
        return path
    return path if rel.startswith("..") else rel


def _check_donation(sp, fn, args, jaxpr, root, findings):
    import jax
    from collections import Counter
    if not sp.donate:
        return
    low = fn.lower(*args)
    info_args, _ = low.args_info
    n_aliased = low.as_text().count("tf.aliasing_output")
    # output avals come from the jaxpr check_program already traced —
    # no third abstract trace
    out_count = Counter((tuple(a.shape), str(a.dtype))
                        for a in jaxpr.out_avals)
    n_before = len(findings)
    for argnum in sp.donate:
        infos = jax.tree_util.tree_leaves(info_args[argnum])
        dropped = [i for i in infos if not i.donated]
        if dropped:
            findings.append(Finding(
                "graph", "graph-donation", _rel(sp.path, root),
                sp.line, "%s.arg%d" % (sp.name, argnum),
                "declared donated arg %d is NOT donated (%d/%d leaves "
                "undonated) — donate_argnums dropped?  Serving HBM "
                "doubles when the pools stop updating in place"
                % (argnum, len(dropped), len(infos))))
            continue
        unmatched = [i for i in infos
                     if out_count[(tuple(i.shape),
                                   str(i.dtype))] == 0]
        if unmatched:
            findings.append(Finding(
                "graph", "graph-donation", _rel(sp.path, root),
                sp.line, "%s.arg%d" % (sp.name, argnum),
                "declared donated arg %d is not in-place-updatable: "
                "%d/%d leaves have no shape/dtype-matched output, so "
                "donation silently stops applying"
                % (argnum, len(unmatched), len(infos))))
            continue
    # aliasing backstop: expected count spans EVERY donated leaf in
    # the lowering (not just registry-declared args) — otherwise an
    # alias newly established on some other donated arg could mask a
    # lost alias on a declared one
    expect_alias = sum(
        1 for arg in info_args
        for i in jax.tree_util.tree_leaves(arg)
        if i.donated and out_count[(tuple(i.shape),
                                    str(i.dtype))] > 0)
    if len(findings) == n_before and n_aliased < expect_alias:
        findings.append(Finding(
            "graph", "graph-donation", _rel(sp.path, root), sp.line,
            sp.name,
            "donation declared and output-matched but the lowering "
            "established only %d/%d input-output aliases — an unused "
            "donated input or an aliasing regression"
            % (n_aliased, expect_alias)))


def _check_budget(sp, jaxpr, budgets, root, findings) -> int:
    peak = peak_live_bytes(jaxpr)
    entry = (budgets or {}).get("programs", {}).get(sp.name)
    sym = sp.name
    if entry is None:
        findings.append(Finding(
            "graph", "graph-hbm-budget", _rel(sp.path, root), sp.line,
            sym, "no hbm_budgets.json entry (peak-live estimate %d "
            "bytes) — run python -m tools.analysis --update-budgets"
            % peak))
    elif peak > entry["budget_bytes"]:
        findings.append(Finding(
            "graph", "graph-hbm-budget", _rel(sp.path, root), sp.line,
            sym, "peak live bytes %d exceed the committed budget %d "
            "(manifest peak %d) — shrink the program or justify a "
            "hand-edited budget" % (peak, entry["budget_bytes"],
                                    entry["peak_bytes"])))
    elif peak > int(entry["peak_bytes"] * (1 + GROWTH)):
        findings.append(Finding(
            "graph", "graph-hbm-budget", _rel(sp.path, root), sp.line,
            sym, "peak live bytes %d grew >%d%% over the manifest's %d "
            "— re-record with --update-budgets if intended"
            % (peak, int(GROWTH * 100), entry["peak_bytes"])))
    return peak


def _check_dtype_drift(sp, jaxpr, root, findings):
    if sp.dtype_region is None:
        return
    allow = sp.f32_allow or {}
    for eqn in _walk_eqns(getattr(jaxpr, "jaxpr", jaxpr)):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0].aval
        dst = eqn.outvars[0].aval
        if str(getattr(dst, "dtype", "")) != "float32":
            continue
        if str(getattr(src, "dtype", "")) not in ("bfloat16", "int8"):
            continue
        shape = src.shape
        if len(shape) == 0 or shape[-1] in allow:
            continue
        loc = _repo_frame(eqn, root) or (_rel(sp.path, root), sp.line)
        findings.append(Finding(
            "graph", "graph-dtype-drift", loc[0], loc[1],
            "%s:%s->f32:last=%d" % (sp.name, src.dtype, shape[-1]),
            "undeclared f32 upcast of a %s %s tensor inside the %s "
            "region of %s (declared accumulation last-dims: %s) — pin "
            "the accumulation dtype or declare the point in the "
            "registry" % (src.dtype, "x".join(map(str, shape)),
                          sp.dtype_region, sp.name,
                          sorted(allow) or "none")))


def _check_host_sync(sp, jaxpr, root, findings):
    if not sp.hot:
        return
    for eqn in _walk_eqns(getattr(jaxpr, "jaxpr", jaxpr)):
        name = eqn.primitive.name
        if _CALLBACK_RE.search(name):
            loc = _repo_frame(eqn, root) or (_rel(sp.path, root),
                                             sp.line)
            findings.append(Finding(
                "graph", "graph-host-sync", loc[0], loc[1],
                "%s:%s" % (sp.name, name),
                "host primitive `%s` inside hot program %s — a host "
                "round-trip per step serializes the device on the "
                "host" % (name, sp.name)))


def check_program(sp: ProgramSpec, root: str,
                  budgets: Optional[Dict] = None) -> List[Finding]:
    """Trace one registered program and run every rule over it."""
    import jax
    fn, args = sp.build()
    jaxpr = jax.make_jaxpr(fn)(*args)
    findings: List[Finding] = []
    _check_donation(sp, fn, args, jaxpr, root, findings)
    _check_budget(sp, jaxpr, budgets, root, findings)
    _check_dtype_drift(sp, jaxpr, root, findings)
    _check_host_sync(sp, jaxpr, root, findings)
    return findings


# ---------------------------------------------------------------------------
# manifest + runner entry points
# ---------------------------------------------------------------------------

def _per_device_expected_peaks(sp, peak: int) -> Optional[Dict]:
    """Per-device (÷tp) expected peaks for the serving step programs,
    recorded next to their manifest entries (round 14).

    The estimator discounts the INPUTS the engine declares tp-sharded
    (heads-sharded pools + megatron-sharded params, from
    ``step_input_specs``): per_device(tp) = peak − sharded_bytes +
    ceil(sharded_bytes / tp).  Intermediates are conservatively left
    replicated (GSPMD shards most of them too, and the XLA gather
    path's merged (T·H) view does re-gather heads), so the number is
    an upper-bound trajectory gate like ``peak_bytes`` itself — the
    point it pins is that the DOMINANT resident state (pools +
    weights) divides by tp.

    Recorded for every mesh-lowerable step entry — round 22 made the
    Pallas step one of them (``paged_attention`` shard_maps over the
    mesh, each device walking its 1/tp heads slice), so its manifest
    row carries ÷tp numbers like the XLA entries'."""
    if sp.name not in ("serving_step", "serving_step_tp",
                       "serving_step_pallas_tp2"):
        return None
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.serving import engine as E
    cfg = _gpt_cfg()
    args = _serving_step_args(cfg)
    declared = E.step_input_specs(args[0], cfg, kv_int8=True)
    leaves = jax.tree_util.tree_leaves(args)
    specs = jax.tree_util.tree_leaves(
        declared, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(specs)
    sharded = sum(_aval_bytes(leaf)
                  for leaf, spec in zip(leaves, specs)
                  if "tp" in tuple(spec))
    return {"tp%d" % tp: int(peak - sharded + math.ceil(sharded / tp))
            for tp in _PER_DEVICE_TPS}


def load_budgets(path: str = None) -> Dict:
    path = path or BUDGETS_PATH
    if not os.path.exists(path):
        return {"version": 1, "programs": {}}
    with open(path) as f:
        return json.load(f)


def _needs_trace(sp, budgets, only: Set[str]) -> bool:
    """--changed-only: a program re-traces when any file in its
    recorded trace closure changed (no recorded closure, or an
    analysis-infra change, always re-traces)."""
    if any(p.startswith("tools/analysis") for p in only):
        return True
    entry = (budgets or {}).get("programs", {}).get(sp.name)
    closure = (entry or {}).get("closure")
    if not closure:
        return True
    # extra_closure unions at READ time only — the stored closure
    # stays a pure trace record (one mechanism, not two)
    return bool((set(closure) | set(sp.extra_closure)) & only)


def run(root: str, only: Optional[Set[str]] = None,
        budgets_path: Optional[str] = None,
        specs: Optional[List[ProgramSpec]] = None,
        budgets: Optional[Dict] = None) -> List[Finding]:
    """Audit every registered program; pragma-filtered findings."""
    if budgets is None:
        budgets = load_budgets(budgets_path)
    if specs is None:
        specs = live_programs()
    findings: List[Finding] = []
    for sp in specs:
        if only is not None and not _needs_trace(sp, budgets, only):
            continue
        findings.extend(check_program(sp, root, budgets))
    # the sharding-readiness rule scopes with the serving step: it
    # re-audits whenever the step program would re-trace (engine /
    # model / analysis-infra changes), or always on a full run
    step_sp = [sp for sp in specs if sp.name == "serving_step"]
    if step_sp and (only is None
                    or _needs_trace(step_sp[0], budgets, only)):
        findings.extend(sharding_readiness_findings(root))
    # the train half (round 19) scopes with the FSDP train step the
    # same way — transformer / parallel.fsdp / analysis-infra changes
    train_sp = [sp for sp in specs if sp.name == "bert_train_step_fsdp"]
    if train_sp and (only is None
                     or _needs_trace(train_sp[0], budgets, only)):
        findings.extend(train_sharding_readiness_findings(root))
    by_path: Dict[str, List[Finding]] = {}
    for f in findings:
        by_path.setdefault(f.path, []).append(f)
    out: List[Finding] = []
    for path, fs in sorted(by_path.items()):
        full = os.path.join(root, path)
        if os.path.exists(full):
            with open(full) as fh:
                fs = apply_pragmas(fs, fh.read())
        out.extend(fs)
    return out


def update_budgets(root: str, path: Optional[str] = None,
                   specs: Optional[List[ProgramSpec]] = None) -> Dict:
    """Re-measure every program (ALWAYS full scope) and rewrite the
    manifest.  ``peak_bytes`` and the trace closure re-record;
    ``budget_bytes`` only ever ratchets DOWN (min of the old budget
    and ceil(peak * HEADROOM)) — the perf-gate never-relax rule.  A
    program whose peak now exceeds its committed budget stays a
    finding until the budget is hand-edited with justification."""
    import jax
    path = path or BUDGETS_PATH
    old = load_budgets(path).get("programs", {})
    programs: Dict[str, Dict] = {}
    for sp in (specs if specs is not None else live_programs()):
        fn, args = sp.build()
        jaxpr = jax.make_jaxpr(fn)(*args)
        peak = peak_live_bytes(jaxpr)
        cand = int(math.ceil(peak * HEADROOM))
        prev = old.get(sp.name)
        budget = cand if prev is None else min(prev["budget_bytes"],
                                               cand)
        programs[sp.name] = {
            "peak_bytes": peak,
            "budget_bytes": budget,
            "closure": sorted(_trace_closure(jaxpr, root)),
        }
        per_dev = _per_device_expected_peaks(sp, peak)
        if per_dev is not None:
            programs[sp.name]["per_device_expected_peak_bytes"] = \
                per_dev
    data = {"version": 1, "programs": programs}
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    return data


# ---------------------------------------------------------------------------
# sharding-readiness audit (report mode)
# ---------------------------------------------------------------------------

def _partition_rules(cfg):
    """Megatron param rules as {tree-path: spec-string}, from
    ``models/transformer.py param_shardings`` over a mesh built by
    ``parallel/mesh.py`` (tp axis present; size irrelevant for the
    rule table)."""
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.parallel.mesh import make_mesh
    import jax
    # dp absorbs whatever devices the host exposes (tier-1 runs with
    # a virtual 8-device CPU mesh); only the axis NAMES matter here
    mesh = make_mesh({"dp": -1, "tp": 1})
    shardings = T.param_shardings(cfg, mesh)
    rules: Dict[str, str] = {}
    for path, ns in jax.tree_util.tree_flatten_with_path(shardings)[0]:
        rules[jax.tree_util.keystr(path)] = "P%s" % (tuple(ns.spec),)
    return rules


def _agg_path(keystr_path: str) -> str:
    """Collapse per-layer indices so the table lists each rule once."""
    return re.sub(r"\[(\d+)\]", "[*]", keystr_path)


def _spec_str(p) -> str:
    return "P%s" % (tuple(p),)


def _derived_spec_strs(rule: str, leaf_key: str) -> Dict[str, str]:
    """Expected declared specs for an int8 ``{"q","s"}`` pair whose
    float weight carries ``rule`` (a ``_spec_str``): ``q`` inherits
    the 2-D rule; the 1-D scale ``s`` takes the rule entry of the dim
    it indexes — per-ROW for the embedding table (``q_rows``), per-
    COLUMN for everything else (``q_cols``).  This is graphlint's OWN
    derivation, independent of ``models/gpt.py decode_param_specs`` —
    the audit verifies the engine's declaration against it."""
    entries = [e.strip() for e in rule[2:-1].rstrip(",").split(",")]
    entries += ["None"] * (2 - len(entries))
    pick = entries[0] if leaf_key.startswith("['tok_emb']") \
        else entries[1]
    return {"q": rule, "s": "P(%s,)" % pick}


_AUDIT_INPUT_NAMES = ["params", "pools", "tokens", "row_slot",
                      "row_pos", "row_live", "bt", "slot_rows"]


def _sharding_rows(cfg):
    """Audit core: every step-program input leaf with its ENGINE-
    DECLARED spec (``serving/engine.py step_input_specs``) verified
    against the megatron rule table.  Returns (rows, counts) where
    counts = {covered, derived, uncovered, mismatched}; a MISMATCH or
    UNCOVERED row is a ``graph-sharding-readiness`` finding."""
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.serving import engine as E

    rules = _partition_rules(cfg)
    args = _serving_step_args(cfg)
    declared = E.step_input_specs(args[0], cfg, kv_int8=True)
    # per-pool-key heads axis: kv (pages, page_size, H, 2*dh) shards
    # axis 2; the round-22 tile-shaped scale planes (pages, 2,
    # page_size, H) shard axis 3 — graphlint derives the expectation
    # from the pool layout itself, independent of the engine's table
    heads_axis_by_key = {"kv": 2, "s": 3}

    rows: List[Tuple[str, str, str, int, str]] = []
    counts = {"covered": 0, "derived": 0, "uncovered": 0,
              "mismatched": 0}
    seen: Set[Tuple[str, str]] = set()
    is_p = lambda x: isinstance(x, P)       # noqa: E731
    for name, arg, dec in zip(_AUDIT_INPUT_NAMES, args, declared):
        leaves = jax.tree_util.tree_flatten_with_path(arg)[0]
        specs = jax.tree_util.tree_flatten_with_path(
            dec, is_leaf=is_p)[0]
        if len(leaves) != len(specs):
            raise RuntimeError(
                "graphlint: declared sharding tree for %r does not "
                "match the program input tree (%d leaves vs %d "
                "specs)" % (name, len(leaves), len(specs)))
        for (path, leaf), (spath, spec) in zip(leaves, specs):
            ks = jax.tree_util.keystr(path)
            if jax.tree_util.keystr(spath) != ks:
                raise RuntimeError(
                    "graphlint: declared spec path %s != input leaf "
                    "path %s under %r"
                    % (jax.tree_util.keystr(spath), ks, name))
            agg = name + _agg_path(ks)
            shape = "x".join(map(str, leaf.shape)) or "scalar"
            if (agg, shape) in seen:
                continue
            seen.add((agg, shape))
            nbytes = _aval_bytes(leaf)
            decs = _spec_str(spec)
            if name == "params":
                expect, how = None, None
                if ks in rules:
                    expect, how = rules[ks], "covered"
                else:
                    m = re.match(r"(.*)\['([qs])'\]$", ks)
                    if m and m.group(1) in rules:
                        expect = _derived_spec_strs(
                            rules[m.group(1)], m.group(1))[m.group(2)]
                        how = "derived(%s)" % m.group(2)
                if expect is None:
                    status = "UNCOVERED — no megatron rule for the " \
                        "declared %s" % decs
                    counts["uncovered"] += 1
                elif decs != expect:
                    status = "MISMATCH — engine declares %s, rule " \
                        "says %s" % (decs, expect)
                    counts["mismatched"] += 1
                elif how == "covered":
                    status = "covered: %s" % decs
                    counts["covered"] += 1
                else:
                    status = "%s: %s from %s" % (how, decs,
                                                 rules[m.group(1)])
                    counts["derived"] += 1
            elif name == "pools":
                entries = tuple(spec)
                m = re.search(r"\['(kv|s)'\]$", ks)
                heads_axis = heads_axis_by_key[m.group(1)] if m else 2
                ok = (len(entries) > heads_axis
                      and entries[heads_axis] == "tp"
                      and all(e is None for i, e in enumerate(entries)
                              if i != heads_axis))
                if ok:
                    status = ("covered: %s — engine-declared, pages "
                              "shard the HEADS axis; block tables / "
                              "free lists / prefix trie stay "
                              "host-side" % decs)
                    counts["covered"] += 1
                else:
                    status = ("MISMATCH — pools must shard exactly "
                              "the heads axis over tp, engine "
                              "declares %s" % decs)
                    counts["mismatched"] += 1
            else:
                if tuple(spec) == ():
                    status = ("covered: P() — engine-declared, "
                              "replicated host-built row/table input")
                    counts["covered"] += 1
                else:
                    status = ("MISMATCH — host-built inputs must "
                              "replicate, engine declares %s" % decs)
                    counts["mismatched"] += 1
            rows.append((agg, shape, str(leaf.dtype), nbytes, status))
    return rows, counts


def sharding_readiness_findings(root: str) -> List[Finding]:
    """The ``graph-sharding-readiness`` rule (round 14): the engine's
    declared step-program shardings (``step_input_specs``) must cover
    EVERY input — params matching the megatron rules (int8 q/s
    derived), pools heads-sharded, host rows replicated.  UNCOVERED
    count must be 0 and covered rows must MATCH; the checked-in
    ``docs/sharding_readiness.md`` renders the same table."""
    import inspect
    from mxnet_tpu.serving import engine as E
    try:
        line = inspect.getsourcelines(E.step_input_specs)[1]
    except (OSError, TypeError):
        line = 1
    path = "mxnet_tpu/serving/engine.py"
    findings: List[Finding] = []
    _, counts = _sharding_rows(_gpt_cfg())
    if counts["uncovered"]:
        findings.append(Finding(
            "graph", "graph-sharding-readiness", path, line,
            "step_input_specs.uncovered",
            "%d serving-step input group(s) have no declared/derivable"
            " sharding — the step program cannot lower through the "
            "mesh for them (see docs/sharding_readiness.md)"
            % counts["uncovered"]))
    if counts["mismatched"]:
        findings.append(Finding(
            "graph", "graph-sharding-readiness", path, line,
            "step_input_specs.mismatch",
            "%d serving-step input group(s) declare shardings that "
            "contradict the megatron rule table / pool layout — "
            "params would silently reshard (or gather) every step"
            % counts["mismatched"]))
    return findings


# ---------------------------------------------------------------------------
# train-step sharding audit (round 19 — the ROADMAP-5 closing criterion)
# ---------------------------------------------------------------------------

def _train_fsdp_derivation(cfg):
    """graphlint's OWN shape-aware derivation of the FSDP composition,
    independent of the ``parallel/fsdp.py`` regex rule table the
    declaration binds: for every param leaf, ``dp`` lands on the FIRST
    dim the megatron rule (``models/transformer.py param_specs``)
    leaves free whose size divides the audit dp degree; a leaf with no
    free divisible dim composes ``dp`` as a sub-axis of its smallest
    already-sharded dim (tp partitions first, dp subdivides the
    shard).  Two independent routes to the same table — a rule-table
    edit that silently changes a param's placement is a MISMATCH."""
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.models import transformer as T

    base = T.param_specs(cfg, tp="tp")
    shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    is_p = lambda x: isinstance(x, P)       # noqa: E731
    base_leaves, treedef = jax.tree_util.tree_flatten(base, is_leaf=is_p)
    shape_leaves = jax.tree_util.tree_leaves(shapes)
    assert len(base_leaves) == len(shape_leaves)
    out = []
    for spec_, leaf in zip(base_leaves, shape_leaves):
        ndim = len(leaf.shape)
        entries = list(spec_)[:ndim]
        entries += [None] * (ndim - len(entries))
        for i in range(ndim):
            if entries[i] is None \
                    and leaf.shape[i] % _AUDIT_DP_SIZE == 0:
                entries[i] = "dp"
                break
        else:
            for i in range(ndim):
                if entries[i] is not None \
                        and leaf.shape[i] % _AUDIT_DP_SIZE == 0:
                    cur = entries[i]
                    entries[i] = (cur + ("dp",)
                                  if isinstance(cur, tuple)
                                  else (cur, "dp"))
                    break
        out.append(P(*entries))
    return jax.tree_util.tree_unflatten(treedef, out)


def _train_sharding_rows(cfg):
    """Audit core for the train step: every declared input spec
    (``models/transformer.py train_step_input_specs`` — what
    ``make_train_step(fsdp=True)`` lowers through) verified against
    the independent derivation; batch rows must shard exactly the
    batch dim over dp, the rng replicates, and the declared OUTPUT
    param specs must equal the input ones (the donation / no-reshard
    contract).  Returns (rows, counts)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.models import transformer as T

    counts = {"covered": 0, "mismatched": 0, "uncovered": 0}
    rows: List[Tuple[str, str, str, int, str]] = []
    try:
        declared, batch_specs, rng_spec = T.train_step_input_specs(
            cfg, tp="tp")
    except Exception as e:                  # rule-table gap
        counts["uncovered"] += 1
        rows.append(("params", "-", "-", 0,
                     "UNCOVERED — train_step_input_specs failed: %s"
                     % e))
        return rows, counts
    derived = _train_fsdp_derivation(cfg)
    shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    is_p = lambda x: isinstance(x, P)       # noqa: E731
    dec_leaves = jax.tree_util.tree_flatten_with_path(
        declared, is_leaf=is_p)[0]
    der_leaves = jax.tree_util.tree_flatten_with_path(
        derived, is_leaf=is_p)[0]
    shp_leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    seen: Set[Tuple[str, str]] = set()
    for (dpath, dec), (_, der), (_, leaf) in zip(dec_leaves, der_leaves,
                                                 shp_leaves):
        agg = "params" + _agg_path(jax.tree_util.keystr(dpath))
        shape = "x".join(map(str, leaf.shape)) or "scalar"
        if (agg, shape) in seen:
            continue
        seen.add((agg, shape))
        decs, ders = _spec_str(dec), _spec_str(der)
        if decs == ders:
            status = ("covered: %s — rule table and shape-aware "
                      "derivation agree" % decs)
            counts["covered"] += 1
        else:
            status = ("MISMATCH — declared %s, derivation says %s"
                      % (decs, ders))
            counts["mismatched"] += 1
        rows.append((agg, shape, str(leaf.dtype), _aval_bytes(leaf),
                     status))
    for name, spec_ in sorted(batch_specs.items()):
        entries = tuple(spec_)
        ok = (len(entries) >= 1 and entries[0] == "dp"
              and all(e is None for e in entries[1:]))
        if ok:
            status = "covered: %s — batch dim sharded over dp" \
                % _spec_str(spec_)
            counts["covered"] += 1
        else:
            status = ("MISMATCH — batch inputs must shard exactly the "
                      "batch dim over dp, declared %s"
                      % _spec_str(spec_))
            counts["mismatched"] += 1
        rows.append(("batch['%s']" % name, "B x T", "-", 0, status))
    if tuple(rng_spec) == ():
        rows.append(("rng", "key", "-", 0,
                     "covered: P() — replicated step key"))
        counts["covered"] += 1
    else:
        rows.append(("rng", "key", "-", 0,
                     "MISMATCH — the step rng must replicate, "
                     "declared %s" % _spec_str(rng_spec)))
        counts["mismatched"] += 1
    out_p, out_loss = T.train_step_output_specs(cfg, tp="tp")
    out_ok = (jax.tree_util.tree_structure(
                  out_p, is_leaf=is_p) == jax.tree_util.tree_structure(
                  declared, is_leaf=is_p)
              and all(_spec_str(a) == _spec_str(b) for (_, a), (_, b)
                      in zip(jax.tree_util.tree_flatten_with_path(
                                 out_p, is_leaf=is_p)[0],
                             dec_leaves))
              and tuple(out_loss) == ())
    if out_ok:
        rows.append(("out: (params', loss)", "-", "-", 0,
                     "covered: params keep the input placement "
                     "(donation contract), loss replicates"))
        counts["covered"] += 1
    else:
        rows.append(("out: (params', loss)", "-", "-", 0,
                     "MISMATCH — output params must keep EXACTLY the "
                     "input placement (a drifted out spec forces a "
                     "reshard every step and breaks donation)"))
        counts["mismatched"] += 1
    return rows, counts


def _train_audit_cfg():
    from mxnet_tpu.models import transformer as T
    return T.bert_tiny(use_flash=False, remat=False, dropout=0.0)


def train_sharding_readiness_findings(root: str) -> List[Finding]:
    """The train half of ``graph-sharding-readiness`` (round 19): the
    FSDP train step's DECLARED in/out specs must cover every param
    (regex rule table agreeing with the shape-aware derivation), shard
    the batch over dp, replicate the rng, and keep the output params
    on the input placement."""
    import inspect
    from mxnet_tpu.models import transformer as T
    try:
        line = inspect.getsourcelines(T.train_step_input_specs)[1]
    except (OSError, TypeError):
        line = 1
    path = "mxnet_tpu/models/transformer.py"
    findings: List[Finding] = []
    _, counts = _train_sharding_rows(_train_audit_cfg())
    if counts["uncovered"]:
        findings.append(Finding(
            "graph", "graph-sharding-readiness", path, line,
            "train_step_input_specs.uncovered",
            "%d train-step input group(s) have no declared/derivable "
            "sharding — the FSDP step cannot lower through the mesh "
            "for them (see docs/sharding_readiness.md)"
            % counts["uncovered"]))
    if counts["mismatched"]:
        findings.append(Finding(
            "graph", "graph-sharding-readiness", path, line,
            "train_step_input_specs.mismatch",
            "%d train-step input/output group(s) declare shardings "
            "that contradict the FSDP composition of the megatron "
            "rule table — params would silently reshard (or gather "
            "full-size) every step" % counts["mismatched"]))
    return findings


def sharding_audit_md(root: str) -> str:
    """The ServingEngine step-program input audit: every input leaf
    with its engine-declared sharding, verified against the megatron
    rules."""
    rows, counts = _sharding_rows(_gpt_cfg())
    lines = [
        "# Sharding readiness — ServingEngine step program",
        "",
        "Report-mode output of graphlint's sharding-readiness audit: "
        "for every",
        "input of the serving step program (registry shapes: gpt_tiny, "
        "%d slots," % _SLOTS,
        "page_size %d, spec_K %d, int8 weights + int8-KV), the "
        "ENGINE'S DECLARED" % (_PAGE, _SPEC_K),
        "sharding (`serving/engine.py step_input_specs` — what "
        "`ServingEngine(tp=N)`",
        "lowers the step through) verified against the megatron "
        "partition rules",
        "(`models/transformer.py param_shardings` over a "
        "`parallel/mesh.py` mesh).",
        "Round 13 this table was the ROADMAP-1 work-list (8 UNCOVERED "
        "groups:",
        "pools + host row vectors); round 14 landed tensor-parallel "
        "serving and",
        "the audit now VERIFIES the engine's declarations — UNCOVERED "
        "or",
        "MISMATCH rows fail tier-1 via the `graph-sharding-readiness` "
        "rule.",
        "",
        "Regenerate: `python -m tools.analysis "
        "--write-sharding-audit`",
        "(`tests/test_static_analysis.py` pins this file current; "
        "`tools/run_static_analysis.sh --changed-only` regenerates it "
        "when",
        "serving/ or models/ change).",
        "",
        "| input | shape | dtype | bytes | partition rule |",
        "|---|---|---|---|---|",
    ]
    for agg, shape, dtype, nbytes, status in rows:
        lines.append("| `%s` | %s | %s | %d | %s |"
                     % (agg, shape, dtype, nbytes, status))
    lines += [
        "",
        "**Summary:** %d covered, %d derived (int8 q/s from the float "
        "rule)," % (counts["covered"], counts["derived"]),
        "UNCOVERED count: %d, mismatched: %d.  Params follow the "
        "megatron rules" % (counts["uncovered"], counts["mismatched"]),
        "(weights tp-sharded, norms/biases-on-unsharded-dims "
        "replicated), the",
        "paged KV pools shard the heads axis over tp (each device "
        "holds 1/tp of",
        "every page), and the host-built row/table int32 vectors "
        "replicate.",
        "Per-device expected peaks for the sharded step live in",
        "`tools/analysis/hbm_budgets.json` "
        "(`per_device_expected_peak_bytes`).",
        "",
    ]
    t_rows, t_counts = _train_sharding_rows(_train_audit_cfg())
    lines += [
        "# Sharding readiness — FSDP BERT train step (round 19)",
        "",
        "The train half of the audit (the ROADMAP-5 closing "
        "criterion): for every",
        "input of the FSDP pretrain step "
        "(`models/transformer.py make_train_step(fsdp=True)`,",
        "bert_tiny shapes, dp composed with tp), the DECLARED "
        "shardings",
        "(`train_step_input_specs` / `train_step_output_specs`) "
        "verified against",
        "graphlint's own shape-aware derivation from the megatron "
        "table — dp on the",
        "first free dim that divides dp=%d, sub-axis composition "
        "when none is free." % _AUDIT_DP_SIZE,
        "The `parallel/fsdp.py` regex rule table and this derivation "
        "are independent",
        "routes; MISMATCH or UNCOVERED rows fail tier-1 via "
        "`graph-sharding-readiness`.",
        "",
        "| input | shape | dtype | bytes | partition rule |",
        "|---|---|---|---|---|",
    ]
    for agg, shape, dtype, nbytes, status in t_rows:
        lines.append("| `%s` | %s | %s | %d | %s |"
                     % (agg, shape, dtype, nbytes, status))
    lines += [
        "",
        "**Summary:** %d covered, UNCOVERED count: %d, mismatched: "
        "%d.  Params and" % (t_counts["covered"],
                             t_counts["uncovered"],
                             t_counts["mismatched"]),
        "param-shaped optimizer moments hold exactly 1/dp per device "
        "(asserted against",
        "live `addressable_shards` in `tests/test_train_scale.py`); "
        "the batch shards its",
        "leading dim over dp; updated params keep the input placement "
        "(donation).",
        "",
    ]
    return "\n".join(lines)
