"""FL run configuration.

``FLRunConfig.algorithm`` is a string resolved through the algorithm
registry (``repro.algorithms.get_algorithm``) — existing configs keep
working, and both it and ``engine`` are validated at construction so a
typo fails immediately with the registered names in the message instead
of deep inside a runtime.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

from repro.algorithms.registry import get_algorithm
from repro.core.client import LocalSpec

ENGINES = ("sequential", "batched")


@dataclass
class FLRunConfig:
    algorithm: str = "vafl"
    num_clients: int = 7
    rounds: int = 200                  # R (server rounds / event budget)
    local: LocalSpec = field(default_factory=LocalSpec)
    target_acc: float = 0.94
    eval_every: int = 1
    seed: int = 0
    # EAFLM constants (paper: xi_d = 1/D, D = 1, alpha = 0.98).  beta and m
    # are unspecified "constant coefficients"; the alpha^2*beta*m^2 product
    # is treated as ONE calibrated constant (m folded into beta, m=1),
    # because m=N's quadratic growth silences the rule entirely for larger
    # federations on our testbed.  beta=1e-2 reproduces the paper's 36-58%
    # suppression range across experiments a-d (benchmarks/table3_ccr.py).
    eaflm_alpha: float = 0.98
    eaflm_beta: float = 1e-2
    # update compression (repro.compress): codec spec for accepted uploads
    # ("identity", "int8", "int4", "topk0.1", "topk0.1_int8", ...) and an
    # optional codec for the model broadcast (no error feedback there —
    # clients train from the lossy model they actually received).
    compressor: str = "identity"
    broadcast_compressor: Optional[str] = None
    error_feedback: bool = True        # SGD-EF residuals on the upload path
    # partial participation: fraction of clients in the round's set S
    # (Algorithm 1 "for each i in S"); 1.0 = all clients every round
    participation: float = 1.0
    # round-based runtime: log per-client test accuracy in every
    # RoundRecord (the paper's Fig. 5/6 data).  This costs one vmapped
    # client eval over ALL clients per round even for algorithms that
    # never read it (afl/eaflm/fedavg) — turn it off at large N; VAFL
    # still computes the accuracies it needs for Eq. 1 regardless.
    record_client_accs: bool = True
    # event-driven runtime
    mix_rate: float = 0.5              # rho
    staleness_kind: str = "poly"       # 'poly' | 'const' | 'hinge'
    events_per_eval: int = 7
    value_backend: Optional[Callable] = None  # optional kernel for ||dg||^2
    # batched async engine (docs/ASYNC_ENGINE.md): engine="batched" keeps
    # per-client state device-resident as stacked pytrees and executes each
    # scheduler window (up to max_batch completions, pop_window) as ONE
    # vmapped local update; accepted uploads flow through a FedBuff-style
    # buffer of buffer_size reconstructions mixed as a staleness-weighted
    # mean.  max_batch=0 means "window = num_clients".  The max_batch=1 +
    # buffer_size=1 configuration reproduces the sequential per-event loop
    # exactly (tests/test_async_engine.py).
    engine: str = "sequential"         # 'sequential' | 'batched'
    max_batch: int = 0                 # pop_window bound (0 = num_clients)
    buffer_size: int = 1               # K reconstructions buffered per mix
    # batched-engine scale layers (docs/ASYNC_ENGINE.md "Sharding" /
    # "Eval fast path"):
    #   shard_clients  — place the stacked per-client state on a 1-D
    #     ("clients",) mesh over the host's devices (NamedSharding on the
    #     leading client axis) so each window's vmapped local update runs
    #     data-parallel across devices.  A 1-device mesh is bit-exact
    #     with the unsharded engine; the device count must divide N, or
    #     the run raises ValueError.  The Pallas ``grad_diff_norm`` value
    #     backend is refused with it: the compiler cannot partition the
    #     kernel inside the value term over the sharded state.
    #   eval_subsample — evaluate the per-client Eq. 1 accuracy term on a
    #     deterministic random subset of this many test samples instead
    #     of the full test set (0 = full).  Applied by the Federation
    #     facade (which holds the test data); low-level callers pass
    #     their own subsampled client_eval_fn (make_evaluator(subsample=)).
    #   eval_cache — refresh each client's Eq. 1 accuracy at most once
    #     every eval_cache of its OWN events, reusing the cached value in
    #     between (0 = recompute every event, the exact semantics).  A
    #     staleness-bounded approximation of Eq. 1's Acc_i term; the
    #     exact global-model eval at record boundaries is never cached
    #     approximately (only reused when the model is bit-identical).
    shard_clients: bool = False
    eval_subsample: int = 0
    eval_cache: int = 0
    # simulation scenario (repro.sim, docs/SCENARIOS.md): a zoo name
    # ("paper_testbed", "mobile_fleet", "flaky_edge", "datacenter", ...)
    # or an explicit repro.sim.ScenarioConfig.  Selects the compute fleet,
    # the byte-aware network model (compressed payload bytes become
    # simulated link delay) and the availability pattern for every
    # runtime.  None — the default — is today's simulation exactly:
    # paper-testbed speeds, free network, always-on clients.
    scenario: Optional[object] = None
    # full-run checkpoint-resume (repro.checkpoint, docs/RESILIENCE.md):
    # checkpoint_path names ONE file written atomically (temp + rename)
    # every checkpoint_every events (sequential/batched/serve) or rounds
    # (rounds/sync).  resume=True restores it when present — the run
    # continues bit-identically — and fails loudly
    # (CheckpointMismatchError) when the file came from a different
    # config or model shape.  checkpoint_every=0 disables writing.
    checkpoint_path: Optional[str] = None
    checkpoint_every: int = 0
    resume: bool = False
    # observability (repro.obs, docs/OBSERVABILITY.md): None (the
    # default) is off with zero overhead; True enables in-memory
    # dual-timeline tracing + metrics with defaults; an
    # repro.obs.ObsConfig (or dict of its fields) selects exporters
    # (JSONL / Chrome trace / console summary / jax.profiler hook).
    # Enabling obs never changes numeric results — golden-seed outputs
    # stay bit-exact with tracing on (tests/test_obs.py).
    obs: Optional[object] = None

    def __post_init__(self):
        get_algorithm(self.algorithm)  # raises ValueError listing names
        if self.engine not in ENGINES:
            raise ValueError(
                f"unknown engine: {self.engine!r}; known engines: "
                f"{', '.join(ENGINES)}")
        if self.scenario is not None:
            # lazy import: repro.sim is only pulled in when a scenario is
            # actually configured
            from repro.sim import resolve_scenario
            self.scenario = resolve_scenario(self.scenario)
        if self.obs is not None:
            # lazy import, mirroring scenario=: repro.obs is only pulled
            # in when observability is actually configured
            from repro.obs import resolve_obs
            self.obs = resolve_obs(self.obs)
        if self.eval_subsample < 0 or self.eval_cache < 0:
            raise ValueError("eval_subsample and eval_cache must be >= 0 "
                             f"(got {self.eval_subsample}, {self.eval_cache})")
        if self.shard_clients and self.value_backend is not None:
            from repro.kernels.grad_diff_norm import ops as gd_ops
            if self.value_backend in (gd_ops.value_backend,
                                      gd_ops.tree_grad_diff_sq_norm):
                raise ValueError(
                    "shard_clients=True cannot run the Pallas grad_diff_norm "
                    "value_backend: the kernel cannot be partitioned over "
                    "the sharded client state; drop one of the two")
        if self.checkpoint_every < 0:
            raise ValueError(
                f"checkpoint_every must be >= 0 (got {self.checkpoint_every})")
        if self.checkpoint_every > 0 and not self.checkpoint_path:
            raise ValueError("checkpoint_every > 0 needs a checkpoint_path")
        if self.resume and not self.checkpoint_path:
            raise ValueError("resume=True needs a checkpoint_path")

    def make_algorithm(self):
        """Resolve this config's algorithm to per-run protocol objects:
        ``(Algorithm spec, UploadPolicy, Aggregator)``."""
        alg = get_algorithm(self.algorithm)
        return alg, alg.make_policy(self), alg.make_aggregator(self)
