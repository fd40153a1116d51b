"""Test harness config: run everything on a virtual 8-device CPU mesh so
multi-chip code paths are exercised without TPU hardware (SURVEY.md §4 /
task brief).  Must run before any jax backend is initialized."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import contextlib

import numpy as _np
import pytest


@contextlib.contextmanager
def pools_seen_on(plat):
    """A ``ServingEngine`` built inside reads ``plat`` as the platform
    its pools live on.  The engine takes its step loop's pipeline depth
    (and, where ``kernel=`` is not given, its attention lowering) from
    ``kernels.platform.platform_of(pools)``, once, in ``__init__``, and
    has no argument for it: a test that needs the TPU's schedule on the
    CPU (``"tpu"``: pipelined) substitutes the observation, not the
    decision, and passes ``kernel="xla"`` itself.  Only construction
    belongs inside: a Pallas call that asks the same question with
    concrete operands would be sent to Mosaic."""
    from mxnet_tpu.kernels import platform
    real = platform.platform_of
    platform.platform_of = lambda *operands: plat
    try:
        yield
    finally:
        platform.platform_of = real

# -- slow-tier split (round-3 verdict #8) -----------------------------------
# The slow tier totals ~15 min on a 1-vCPU host — too long for one sitting.
# Each slow-marked MODULE is assigned to one of four balanced groups, each
# ≤~4.5 min, so CI/judges can run `pytest -m slow_a` … `-m slow_d` inside
# standard timeouts (tools/run_slow_tier.sh runs all four).  Measured
# per-file times: 2026-07-31 (this conftest).  Unlisted new slow modules
# land in slow_d by default.
_SLOW_GROUPS = {
    # group a: ~207s
    "test_train_convergence": "a", "test_vision_ops": "a",
    "test_test_utils": "a",
    # group b: ~219s
    "test_registry_sweep": "b", "test_dtype_matrix": "b",
    "test_operator_grad_sweep": "b", "test_operator": "b",
    "test_numpy": "b", "test_sparse": "b", "test_longtail_ops": "b",
    # group c: ~250s
    "test_pipeline_moe": "c", "test_parallel": "c",
    "test_ring_attention": "c",
    # group d: ~220s (everything else, incl. test_serving — the
    # continuous-batching engine, round 7)
    "test_serving": "d",
    # group e: ~4min — the collective-matrix pins compile 6 parallel
    # configs' steady-state train steps; too heavy to share a group
    "test_collective_matrix": "e",
    # group f: ~1min — the round-10 serving cluster (multi-replica
    # worker threads + watchdog timing); its own group so thread-
    # scheduling jitter never stretches group d past its budget
    "test_serving_cluster": "f",
    # group g: ~2min — round-11 in-engine speculation + paged-
    # attention kernel combos (every (kernel, spec_K) pair compiles a
    # fresh step program; isolated for the same budget reason as f)
    "test_serving_spec": "g",
    # group h: ~2min — round-12 interleaving explorer (>=200 seeded
    # schedules through the cluster; its own group so the sweep's
    # schedule count can grow without squeezing group f's budget)
    "test_interleave": "h",
    # group i: ~2.5min — round-14 tensor-parallel serving (every tp
    # config compiles a mesh-lowered step program on the virtual
    # 8-device mesh; isolated for the same compile-budget reason as g)
    "test_serving_tp": "i",
    # group j: ~4min — round-15 disaggregated prefill/decode serving
    # (each test spawns 2-3 worker OS processes that each import jax
    # and compile a step program; isolated so the per-test process
    # spawn cost never squeezes another group's budget)
    "test_serving_disagg": "j",
    # group k: ~3min — round-16 traffic realism (seeded trace replay,
    # autoscaler up/down with the zero-leak drain contract, chaos
    # kill/stall under burst vs the generate oracle; own group
    # because the scenarios pace themselves on the wall clock and
    # replica-thread scheduling jitter must not squeeze f/h)
    "test_serving_traffic": "k",
    # group l: ~2min — round-18 KV tiering (scripted pressure/spill
    # scenarios over tight pools; own group so the per-test engine
    # compiles never squeeze d/f)
    "test_serving_tier": "l",
    # group m: ~2min — round-19 training scale-out (FSDP/ICI-kvstore
    # exactness + byte-accounting; every config compiles its own
    # sharded train step on the virtual mesh, so the group is
    # isolated for the same compile-budget reason as e/g/i)
    "test_train_scale": "m",
    # group n: ~3min — round-20 HTTP/SSE front door (each scenario
    # runs a live asyncio server thread over a real cluster and paces
    # on the wall clock; own group so socket/scheduling jitter never
    # squeezes f/k)
    "test_http_frontend": "n",
    # group o: ~2min — round-21 latency-hiding overlap (every
    # scenario compiles the tok_src step variant on top of the
    # serial program, and the disagg case spawns worker processes;
    # own group so the double compile bill never squeezes d/f/j)
    "test_serving_overlap": "o",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker("slow") is not None:
            mod = item.module.__name__.rsplit(".", 1)[-1]
            group = _SLOW_GROUPS.get(mod, "d")
            item.add_marker(getattr(pytest.mark, "slow_" + group))


@pytest.fixture(autouse=True)
def _seed_all():
    """Seeded-reproducible tests (reference: @with_seed decorator in
    tests/python/unittest/common.py)."""
    seed = int(os.environ.get("MXNET_TEST_SEED", "42"))
    _np.random.seed(seed)
    import mxnet_tpu as mx
    mx.random.seed(seed)
    yield
