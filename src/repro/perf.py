"""Performance-experiment flags (§Perf hillclimbing).

Every optimization is gated so the paper-faithful baseline and each
optimized variant can be compiled from the same tree:

  REPRO_PERF="bf16_experts,gqa_grouped,prob_bf16,microbatch=4" \
      python -m repro.launch.dryrun ...

or programmatically ``perf.set_flags(bf16_experts=True)`` (tests use this to
assert numerical parity between paths).  Flags are read at TRACE time; a
process sees a consistent setting.

Flags:
  bf16_experts  — MoE expert matmuls read bf16 operands with fp32 MXU
                  accumulation (instead of materializing fp32 casts of the
                  all-gathered expert weights).
  gqa_grouped   — GQA attention contracts (B, Hkv, G, S, D) grouped einsums
                  instead of jnp.repeat'ing K/V to Hq (removes the group-
                  factor from K/V bytes).
  prob_bf16     — attention probabilities cast to bf16 for the p·V matmul
                  (max/lse stay fp32; flash-attention standard practice).
  microbatch=N  — grad-accumulation over N microbatches inside the train
                  step (activation temp ÷ N; grads reduced once).
  opt_all       — shorthand for every boolean flag above.

Topology-analytics flags (the batched all-source BFS/Brandes engine behind
``repro.core.utilization``):
  util_engine=NAME — which arc-load engine to use: ``auto`` (default),
                  ``naive`` (the per-source reference loops), ``numpy``
                  (batched level-synchronous GEMM engine; bipartite graphs
                  run on half-size biadjacency blocks, graphs beyond
                  util_dense_max fall back to a CSR reduceat sweep),
                  ``csr`` (force the sparse sweep), ``jax`` (jnp GEMMs,
                  jit-compiled, chunked over source blocks), ``pallas``
                  (the jax recurrences through the fused mask+GEMM
                  kernels of repro.kernels.mask_gemm — compiled on TPU,
                  pallas-interpreter float64 elsewhere), or ``orbit``
                  (force the automorphism shortcut; errors if the family
                  has no known generators).
  util_orbits=0 — disable the orbit shortcut inside ``auto``.  The
                  shortcut runs one Brandes sweep per automorphism vertex
                  orbit (1–2 for PN/demi-PN/MMS/Hamming, 2 for OFT column
                  symmetry) and reconstructs exact per-arc loads from
                  arc-orbit averages; it is exact, not approximate — this
                  flag exists to measure the exact engines.  It also
                  gates the weighted path's uniform-demand rerouting
                  (``arc_loads_weighted`` detects ``w * (ones - I)``
                  demand — incl. spread collectives and the Valiant
                  phases of any permutation — and runs the uniform
                  engines instead of a full weighted sweep).
  util_dense_max=N — largest vertex count that uses dense (N, N)
                  adjacency GEMMs (default 6144); beyond it auto prefers
                  jax (up to util_jax_max) then CSR.
  util_jax_max=N — largest vertex count auto will hand to the jax dense
                  engine (default 12288).
  util_block=N  — source-block row count for the batched engines
                  (0 = size blocks to ~48 MB of working set).

e.g. ``REPRO_PERF="util_engine=naive" python -m benchmarks.run`` times the
paper tables on the reference implementation.

Flow-level simulator flags (repro.sim):
  sim_backend=NAME — default backend for ``SimConfig(backend="auto")``:
                  auto | numpy | jax | pallas | pallas_interpret.

Observability (repro.obs):
  obs=MODE      — default mode for ``obs.session()`` calls that do not
                  pin one: ``none`` (default; spans/counters are shared
                  no-op singletons), ``metrics``, or ``trace``
                  (Chrome-trace spans + metrics).  See
                  docs/observability.md.
"""

from __future__ import annotations

import dataclasses
import os

__all__ = ["PerfFlags", "flags", "set_flags", "from_env"]


@dataclasses.dataclass
class PerfFlags:
    bf16_experts: bool = False
    gqa_grouped: bool = False
    prob_bf16: bool = False
    microbatch: int = 1
    # MoE dispatch enters shard_map in the residual's natural (B, S, M)
    # layout (batch->dp axes, seq->model) and flattens INSIDE the body.
    # The baseline's (B·S, M) flatten has no efficient SPMD lowering from
    # the 2-axis layout, so GSPMD replicates the full activation every MoE
    # layer ('involuntary full rematerialization' warnings).
    # DEFAULT ON after the §Perf hillclimb (-67% collective on deepseek
    # train_4k, routing-identical); baselines reproduce with
    # REPRO_PERF=moe_3d=0.
    moe_3d: bool = True
    # ZeRO-1 grad path in the dry-run's train step (reduce-scatter grads +
    # all-gather updated params instead of all-reduce)
    zero1: bool = False
    # When a model's head count does not divide the model axis (smollm: 9
    # heads, mamba2: 24 SSD heads, vs 16-way TP), the baseline replicates
    # the whole mixer on the model axis (16x flops+bytes).  This flag
    # spreads BATCH over the model axis inside such blocks instead — pure
    # DP where TP has nothing to shard.
    dp_over_model: bool = False
    # Override the SSD chunk length (0 = use the arch config).  Intra-chunk
    # score/decay streams scale with chunk Q (total ~ L·Q elements), so
    # smaller chunks trade matmul shape for bytes.
    ssd_chunk: int = 0
    # Replicate ff-dim weight shards (rules ff->None).  Pairs with
    # dp_over_model on small models: model-sharded conv/MLP weights force
    # a batch-(data,model) -> channel-model activation transition that
    # GSPMD can only do by full replication (observed on mamba2: 382 GB/dev
    # all-gather).  Replicated weights make those blocks pure local DP.
    replicate_ff: bool = False
    # Arc-load engine selection for repro.core.utilization (see module
    # docstring): auto | naive | numpy | csr | jax | pallas | orbit.
    util_engine: str = "auto"
    # Let `auto` use the automorphism-orbit shortcut (exact; one Brandes
    # sweep per vertex orbit instead of per vertex).
    util_orbits: bool = True
    # Size thresholds for auto's exact-engine choice.
    util_dense_max: int = 6144
    util_jax_max: int = 12288
    # Source-block rows for the batched engines (0 = auto ~48 MB blocks).
    util_block: int = 0
    # BLAS threads while inside the dense engines (0 = leave the pool
    # alone).  The per-level GEMMs are a few hundred rows square, where
    # OpenBLAS threading measures 3-4x SLOWER than one core.
    util_blas_threads: int = 1
    # Python threads running independent source-block sweeps (numpy
    # releases the GIL in GEMM/ufunc loops, so 2 single-BLAS-thread sweeps
    # overlap ~perfectly on 2 cores).  1 = sequential.
    util_workers: int = 2
    # Flow-level simulator backend (repro.sim): auto | numpy | jax |
    # pallas | pallas_interpret.  auto picks the jit-compiled jax step
    # for large (N * degree * dests) instances, the numpy reference
    # otherwise, and the fused blocked sparse-dest kernel step
    # (repro.sim.kernel — the pallas kernels on TPU, their jnp bodies
    # elsewhere) once the dense cell count exceeds engine.SIM_MAX_CELLS;
    # pallas_interpret runs the actual kernels through the pallas
    # interpreter (parity testing).  SimConfig(backend=...) overrides per
    # run.
    sim_backend: str = "auto"
    # Observability default mode for repro.obs sessions opened without an
    # explicit mode: none (off — every span/counter helper returns a
    # shared no-op singleton, the hot paths pay one global read), metrics
    # (counters/gauges/histograms), or trace (spans too, exportable as
    # Chrome-trace JSON).  Nothing records until obs.session() is
    # entered; REPRO_PERF=obs=trace makes every such session trace.
    obs: str = "none"


_FLAGS = PerfFlags()


def flags() -> PerfFlags:
    return _FLAGS


def set_flags(**kw) -> PerfFlags:
    for k, v in kw.items():
        if not hasattr(_FLAGS, k):
            raise KeyError(k)
        setattr(_FLAGS, k, v)
    return _FLAGS


def from_env(env: str | None = None) -> PerfFlags:
    """Parse REPRO_PERF and apply."""
    spec = env if env is not None else os.environ.get("REPRO_PERF", "")
    for tok in filter(None, (t.strip() for t in spec.split(","))):
        if tok == "opt_all":
            set_flags(bf16_experts=True, gqa_grouped=True, prob_bf16=True,
                      moe_3d=True)
        elif "=" in tok:
            k, v = tok.split("=", 1)
            try:
                val: int | str = int(v)
            except ValueError:
                val = v  # string-valued flags, e.g. util_engine=numpy
            set_flags(**{k: val})
        else:
            set_flags(**{tok: True})
    return _FLAGS


from_env()
