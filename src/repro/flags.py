"""Perf-variant knobs for the §Perf hillclimb.

Defaults reproduce the BASELINE; the dry-run's --variant flag (e.g.
``--variant mla_decomp+accum8+sp``) flips knobs so each hypothesis gets its
own lowered artifact, before/after recorded side by side in EXPERIMENTS.md.
"""

from __future__ import annotations

FLAGS = {
    # MLA: use the decompressed (per-head K/V) formulation for s>1 paths
    # instead of the absorbed latent form (hypothesis: absorbed q_lat/lat
    # accumulators of (B,S,H,kv_lora) fp32 dominate train/prefill memory).
    "mla_decomp": False,
    # gradient accumulation: microbatch the train step (activation memory /
    # accum_steps at the cost of accum_steps serial sub-steps).
    "accum_steps": 1,
    # sequence parallelism: keep inter-layer activations sequence-sharded on
    # the model axis (norms/residuals run sharded; reduce-scatter+all-gather
    # replaces all-reduce around TP blocks).
    "sp": False,
    # sp2: additionally keep attention QUERIES sequence-sharded (each query
    # attends the full gathered K/V — K/V bytes are GQA-small, so the
    # per-layer gather shrinks from activations (S*d) to caches (S*K*hd)).
    "sp_attn": False,
    # MoE dispatch capacity factor override (None = config value)
    "moe_cf": None,
    # span engine gain backend.  "auto" (default) dispatches per bucket: gain
    # rounds whose word count is below span_dispatch_threshold run on numpy
    # (bitwise_count, the oracle), larger ones on the accelerated path (the
    # Pallas span_gain kernel on TPU, the jitted jnp popcount elsewhere).
    # "numpy" / "jax" / "pallas" pin one backend globally; "pallas" is the
    # compiled kernel and raises off TPU, "interpret" runs the kernel body
    # in the Pallas interpreter (CPU tests).  Every backend is bit-identical,
    # so the flag is purely a performance knob.
    "span_backend": "auto",
    # auto-dispatch crossover, in gain-matrix words (A * N * W) per greedy
    # round.  Calibrated by benchmarks/kernel_bench.py (span_gain_calibration
    # rows): on this container numpy's bitwise_count wins below ~30-70k words
    # and the jitted backend past that (dispatch + uint32-view overhead
    # amortized), so the default sits mid-band.
    "span_dispatch_threshold": 48_000,
    # whole-round cover-loop backend.  "auto" (default) dispatches per
    # BUCKET: buckets whose packed gain work (B * N * W words) is below
    # span_round_threshold run the per-round host loop (one numpy/jax gain
    # matrix per greedy round, the PR 5 engine); larger buckets run the
    # device-resident round loop — packed membership words and cover state
    # are uploaded once and a jitted lax.while_loop fuses
    # mask+popcount+argmax+scatter across ALL greedy rounds, so the bucket
    # costs one host<->device transfer total instead of one per round.
    # "numpy" / "device" pin one path.  Bit-identical covers either way
    # (integer popcount, argmax ties -> lowest partition id), so this is
    # purely a performance knob.
    "span_round_backend": "auto",
    # auto crossover for span_round_backend, in packed words (B * N * W)
    # per bucket.  Calibrated by benchmarks/kernel_bench.py
    # (span_round_calibration rows): the jitted loop amortizes its dispatch
    # + compile-cache lookup over every greedy round, so it wins earlier
    # than the per-round threshold; small refresh buckets (a few edges
    # after an LMBR move) stay on numpy.
    "span_round_threshold": 200_000,
    # LMBR Algorithm-5 peel backend.  "vector" (default) projects the
    # missed pairs in one batch (flat pin-attribution projection) and peels
    # each pair's trajectory on the host with a heap (the lockstep numpy
    # peel under lmbr_epochs="partition"); "reference" the retained
    # pure-Python oracle; "device" the
    # jitted dense lockstep peel (repro.kernels.lockstep_peel, jnp path);
    # "pallas" the compiled Pallas lockstep-peel kernel (raises off TPU);
    # "interpret" the same kernel in the Pallas interpreter (CPU tests).
    # Device backends emit the free-space-independent peel TRAJECTORY in
    # integer-exact f32 and the (gain, subset) selection happens on host in
    # f64 — shared with the cache re-evaluation path — so results stay
    # bit-identical (same subsets, same gains, same tie-breaks) and the
    # flag is purely a performance knob; benchmarks/bench_lmbr.py and
    # benchmarks/kernel_bench.py time the backends.  Device peels require
    # integer-valued weights below 2^24 (checked per workload); outside that
    # domain the pairs run "vector" and the fit's stats count the skip
    # (dense_peel_skips).
    "lmbr_peel": "vector",
    # LMBR gain-cache granularity.  "item" (default) keys cache validity on
    # a global move tick: each cached (src, dest) entry stores the tick it
    # was filled at, its shared-edge set + count, and its candidate pool;
    # it stays valid while the pair's shared-edge count is unchanged (O(1)
    # Gram-matrix lookup), no shared edge was re-stamped by a later cover
    # recompute (per-edge tick), and no pooled item gained residency after
    # the fill (per-item tick) — so untouched candidate pools survive moves
    # that only graze their partitions, with a projection-fingerprint second
    # level and re-evaluable cached peel trajectories behind it.
    # "partition" restores the PR 5 cache (per-partition cov/mem epochs,
    # <1% hit rate under the move loop).  Both are exactness-neutral; the
    # bench's engine comparison pins "partition" for the baseline rows.
    "lmbr_epochs": "item",
    # epoch-keyed (src, dest) -> (gain, items) memo in the LMBR move loop:
    # a pair is only re-peeled when a partition epoch it depends on moved
    # (cover/pin-attribution epoch of either side, membership epoch of the
    # destination).  Exactness-neutral; off reproduces the uncached engine.
    "lmbr_gain_cache": True,
    # online router: queries per batched_cover_csr call in the streaming
    # replica-selection router (repro.online.ReplicaRouter).  Calibrated by
    # benchmarks/bench_online.py's router sweep: big enough to amortize the
    # per-call bitset packing, small enough that every gain round stays in
    # the numpy band of the span dispatch rule.
    "router_microbatch": 384,
    # online router: load-aware tie-break.  Off (default) the router is
    # bit-identical to per-query cover_for_query (ties -> lowest partition
    # id); on, same-gain covers prefer the partition with the lowest entry in
    # the router's running access-load ledger (power-of-two-choices style).
    "router_balance": False,
    # drift detector: sliding window size W (queries) for the workload sketch
    # and the windowed avg_span monitor.
    "drift_window": 512,
    # drift detector: refit trigger — fires when the windowed avg_span
    # exceeds (fit-time baseline) * drift_threshold.
    "drift_threshold": 1.25,
    # online router (balanced mode): stale-ledger tolerance for the
    # tie-break row permutation.  The lexsort over (load, id) is only
    # rebuilt when some partition's ledger load has shifted by more than
    # epsilon * max(load at last sort, 1.0) since the last sort.  0.0 (the
    # default) rebuilds on ANY shift — bit-identical to re-sorting every
    # microbatch (an unchanged ledger lexsorts to the same permutation);
    # > 0 keeps the lexsort off the steady-state hot path at the cost of
    # routing against a slightly stale load ordering (spans are unaffected
    # — only which equal-gain replica serves).
    "router_ledger_epsilon": 0.0,
    # cluster-scale sharded fit (repro.scale): number of workload shards.
    # 0 = auto (max(1, num_partitions // 8)); explicit values pin the
    # decomposition width.
    "scale_shards": 0,
    # cluster-scale sharded fit: per-shard fit processes.  1 (default) runs
    # the deterministic serial path; > 1 dispatches shards onto a process
    # pool (results are merged in shard order, so worker count never
    # changes the fitted placement — asserted by tests/test_scale.py).
    "scale_workers": 1,
    # cluster-scale sharded fit: LMBR move budget for the bounded repair
    # pass restricted to cross-shard boundary edges after the merge
    # (0 disables the pass; repair only ever copies into free space, so it
    # is capacity-safe by construction).
    "scale_boundary_repair": 256,
    # placement objective.  "span" (default) is the paper's objective:
    # balance load across all partitions and minimize average span.
    # "energy" concentrates the fit onto a capacity-descending prefix of
    # ACTIVE partitions (smallest prefix holding ~1.25x the total item
    # weight) so the remaining rows stay empty and can be powered down —
    # the LMBR cold start and its dest_mask are restricted to the active
    # set; the simulator reports active_machines / cluster power per fit.
    "placement_objective": "span",
    # per-item durability ceiling eps for Π p_fail ≤ eps (independent
    # partition failures, repro.core.cluster).  0.0 (default) disables the
    # constraint; > 0 makes PlacementService fits add greedy low-fail-prob
    # replicas post-fit until every item meets the ceiling (capacity-safe,
    # validated by validate_durability).
    "durability_eps": 0.0,
    # LMBR gain penalty weight for destination access cost: a candidate
    # move's gain is charged node_cost_weight * access_cost[dest] before
    # the accept test, steering replicas toward cheap nodes.  0.0 (default)
    # is bit-identical to the unpenalized engine; only engages when the fit
    # is given a per-partition cost vector (NodeProfile.access_cost).
    "node_cost_weight": 0.0,
    # online router (balanced mode): cost-aware tie-break.  Off (default)
    # equal-gain covers prefer the least-loaded partition.  On, the ledger
    # permutation sorts by load * routing_cost (access cost + normalized
    # active power from the NodeProfile) — a uniform profile gives a
    # constant cost vector, so the permutation (and every routing decision)
    # stays bit-identical to least-loaded.
    "router_cost_aware": False,
    # live plan migration: transfer bandwidth in item-weight units per
    # served query (the executor's tick).  0.0 (the default) keeps the
    # legacy ATOMIC hot-swap — plan changes in run_online (drift refits,
    # "migrate" events) apply instantly between microbatches, bit-identical
    # to the pre-migration behavior.  > 0 streams the plan diff as paced
    # replica transfers through repro.online.migration, serving from the
    # union layout until every copy lands.
    "migration_bandwidth": 0.0,
    # live plan migration: maximum concurrent in-flight transfers per
    # DESTINATION partition (rucio-conveyor-style per-destination
    # throttling).  Together with the largest scheduled copy this bounds
    # the concurrent in-flight bytes by construction
    # (MigrationPlan.inflight_bound).
    "migration_concurrency": 4,
    # live plan migration: capacity slack fraction for the union layout —
    # a transfer only starts while the destination's committed + reserved
    # load stays within capacity * (1 + headroom).  Too-tight headroom on
    # a diff whose copies all wait on drops raises RuntimeError instead of
    # deadlocking silently.
    "migration_headroom": 0.10,
    # observability level (repro.obs).  "off" (default) swaps in the no-op
    # null registry/tracer — zero allocations on hot paths, bit-identical
    # and timing-neutral (gated by benchmarks/bench_obs.py).  "counters"
    # turns on the metrics registry (counters/gauges/histograms, Prometheus
    # exposition via to_prom_text); "trace" additionally records nested
    # spans/events as Chrome-trace JSON (to_chrome_trace).  No level may
    # change results: hooks only observe.
    "obs_level": "off",
    # run_online: emit a periodic metrics snapshot (registry gauges + a
    # Chrome-trace counter event when tracing) every N served queries.
    # 0 (default) disables periodic snapshots.
    "obs_snapshot_every": 0,
    # health monitoring (repro.obs.health): arm the HealthMonitor inside
    # run_online.  Requires obs_level != "off" AND obs_snapshot_every > 0
    # (the monitor consumes the periodic snapshots) — run_online raises
    # ValueError otherwise.  Monitoring is read-only: it changes no
    # placement, routing, or stats values (same contract as obs_level).
    "obs_health": False,
    # health: window size in SNAPSHOTS for every windowed SLO rule (avg
    # span, degraded rate, load skew, p99 latency, backlog).
    "health_window": 8,
    # health: consecutive clear evaluations before a firing alert
    # resolves (hysteresis; firing happens on the first breach).
    "health_hysteresis": 2,
    # health SLO thresholds — 0 disables the individual rule:
    # windowed avg span / fit-time baseline ratio ceiling,
    "health_span_slo": 1.5,
    # p99 serve.microbatch latency ceiling in seconds (from the
    # router_microbatch_seconds histogram; wall-clock, so 0/off by
    # default — enable for real deployments, not unit tests),
    "health_p99_slo": 0.0,
    # windowed degraded-query rate ceiling (degraded / attempted),
    "health_degraded_slo": 0.02,
    # windowed per-partition load-delta skew ceiling (p99 / mean),
    "health_skew_slo": 4.0,
    # windowed mean migration in-flight backlog ceiling (item-weight
    # units; 0/off by default — only meaningful with paced migrations),
    "health_backlog_slo": 0.0,
    # health: EWMA z-score anomaly detection threshold on every rule's
    # value stream (|z| above this fires "<rule>_anomaly" through the
    # same state machine).  0 (default) disables anomaly rules.
    "health_anomaly_z": 0.0,
}


def set_variant(spec: str):
    """'mla_decomp+accum8+sp+cf1.0' -> flag settings."""
    reset()
    for part in filter(None, spec.split("+")):
        if part == "baseline":
            continue
        elif part == "mla_decomp":
            FLAGS["mla_decomp"] = True
        elif part.startswith("accum"):
            FLAGS["accum_steps"] = int(part[len("accum"):])
        elif part == "sp":
            FLAGS["sp"] = True
        elif part == "sp2":
            FLAGS["sp"] = True
            FLAGS["sp_attn"] = True
        elif part.startswith("cf"):
            FLAGS["moe_cf"] = float(part[2:])
        elif part.startswith("spanth"):
            FLAGS["span_dispatch_threshold"] = int(part[len("spanth"):])
        elif part.startswith("spanroundth"):
            FLAGS["span_round_threshold"] = int(part[len("spanroundth"):])
        elif part.startswith("spanround"):
            backend = part[len("spanround"):]
            if backend not in ("auto", "numpy", "device"):
                raise ValueError(f"unknown span round backend {backend!r}")
            FLAGS["span_round_backend"] = backend
        elif part.startswith("peel"):
            backend = part[len("peel"):]
            if backend not in ("vector", "reference", "device", "pallas",
                               "interpret"):
                raise ValueError(f"unknown lmbr peel backend {backend!r}")
            FLAGS["lmbr_peel"] = backend
        elif part.startswith("lmbrepoch"):
            mode = part[len("lmbrepoch"):]
            if mode not in ("item", "partition"):
                raise ValueError(f"unknown lmbr epoch mode {mode!r}")
            FLAGS["lmbr_epochs"] = mode
        elif part.startswith("lmbrcache"):
            FLAGS["lmbr_gain_cache"] = bool(int(part[len("lmbrcache"):]))
        elif part.startswith("routereps"):
            eps = float(part[len("routereps"):])
            if eps < 0:
                raise ValueError(f"router_ledger_epsilon must be >= 0, got {eps}")
            FLAGS["router_ledger_epsilon"] = eps
        elif part.startswith("routerbal"):
            FLAGS["router_balance"] = bool(int(part[len("routerbal"):]))
        elif part.startswith("routermb"):
            FLAGS["router_microbatch"] = int(part[len("routermb"):])
        elif part.startswith("shards"):
            shards = int(part[len("shards"):])
            if shards < 0:
                raise ValueError(f"scale_shards must be >= 0, got {shards}")
            FLAGS["scale_shards"] = shards
        elif part.startswith("scalew"):
            workers = int(part[len("scalew"):])
            if workers < 1:
                raise ValueError(f"scale_workers must be >= 1, got {workers}")
            FLAGS["scale_workers"] = workers
        elif part.startswith("brepair"):
            moves = int(part[len("brepair"):])
            if moves < 0:
                raise ValueError(
                    f"scale_boundary_repair must be >= 0, got {moves}"
                )
            FLAGS["scale_boundary_repair"] = moves
        elif part.startswith("driftw"):
            FLAGS["drift_window"] = int(part[len("driftw"):])
        elif part.startswith("driftth"):
            FLAGS["drift_threshold"] = float(part[len("driftth"):])
        elif part == "energy":
            FLAGS["placement_objective"] = "energy"
        elif part.startswith("durab"):
            eps = float(part[len("durab"):])
            if eps < 0:
                raise ValueError(f"durability_eps must be >= 0, got {eps}")
            FLAGS["durability_eps"] = eps
        elif part.startswith("nodecost"):
            w = float(part[len("nodecost"):])
            if w < 0:
                raise ValueError(f"node_cost_weight must be >= 0, got {w}")
            FLAGS["node_cost_weight"] = w
        elif part.startswith("routercost"):
            FLAGS["router_cost_aware"] = bool(int(part[len("routercost"):]))
        elif part.startswith("migbw"):
            bw = float(part[len("migbw"):])
            if bw < 0:
                raise ValueError(f"migration_bandwidth must be >= 0, got {bw}")
            FLAGS["migration_bandwidth"] = bw
        elif part.startswith("migconc"):
            conc = int(part[len("migconc"):])
            if conc < 1:
                raise ValueError(
                    f"migration_concurrency must be >= 1, got {conc}"
                )
            FLAGS["migration_concurrency"] = conc
        elif part.startswith("mighead"):
            head = float(part[len("mighead"):])
            if head < 0:
                raise ValueError(f"migration_headroom must be >= 0, got {head}")
            FLAGS["migration_headroom"] = head
        elif part.startswith("obshealth"):
            FLAGS["obs_health"] = bool(int(part[len("obshealth"):]))
        elif part.startswith("obssnap"):
            every = int(part[len("obssnap"):])
            if every < 0:
                raise ValueError(f"obs_snapshot_every must be >= 0, got {every}")
            FLAGS["obs_snapshot_every"] = every
        elif part.startswith("obs"):
            lv = part[len("obs"):]
            if lv not in ("off", "counters", "trace"):
                raise ValueError(f"unknown obs level {lv!r}")
            FLAGS["obs_level"] = lv
        elif part.startswith("healthw"):
            w = int(part[len("healthw"):])
            if w < 2:
                raise ValueError(f"health_window must be >= 2, got {w}")
            FLAGS["health_window"] = w
        elif part.startswith("healthhyst"):
            h = int(part[len("healthhyst"):])
            if h < 1:
                raise ValueError(f"health_hysteresis must be >= 1, got {h}")
            FLAGS["health_hysteresis"] = h
        elif part.startswith("healthspan"):
            FLAGS["health_span_slo"] = float(part[len("healthspan"):])
        elif part.startswith("healthp99"):
            FLAGS["health_p99_slo"] = float(part[len("healthp99"):])
        elif part.startswith("healthdeg"):
            FLAGS["health_degraded_slo"] = float(part[len("healthdeg"):])
        elif part.startswith("healthskew"):
            FLAGS["health_skew_slo"] = float(part[len("healthskew"):])
        elif part.startswith("healthbacklog"):
            FLAGS["health_backlog_slo"] = float(part[len("healthbacklog"):])
        elif part.startswith("healthz"):
            z = float(part[len("healthz"):])
            if z < 0:
                raise ValueError(f"health_anomaly_z must be >= 0, got {z}")
            FLAGS["health_anomaly_z"] = z
        elif part.startswith("span"):
            backend = part[len("span"):]
            if backend not in ("auto", "numpy", "jax", "pallas", "interpret"):
                raise ValueError(f"unknown span backend {backend!r}")
            FLAGS["span_backend"] = backend
        else:
            raise ValueError(f"unknown variant component {part!r}")


def reset():
    FLAGS.update(mla_decomp=False, accum_steps=1, sp=False, sp_attn=False,
                 moe_cf=None, span_backend="auto",
                 span_dispatch_threshold=48_000, span_round_backend="auto",
                 span_round_threshold=200_000, lmbr_peel="vector",
                 lmbr_epochs="item",
                 lmbr_gain_cache=True,
                 router_microbatch=384, router_balance=False,
                 drift_window=512, drift_threshold=1.25,
                 router_ledger_epsilon=0.0, scale_shards=0, scale_workers=1,
                 scale_boundary_repair=256, placement_objective="span",
                 durability_eps=0.0, node_cost_weight=0.0,
                 router_cost_aware=False, migration_bandwidth=0.0,
                 migration_concurrency=4, migration_headroom=0.10,
                 obs_level="off", obs_snapshot_every=0, obs_health=False,
                 health_window=8, health_hysteresis=2, health_span_slo=1.5,
                 health_p99_slo=0.0, health_degraded_slo=0.02,
                 health_skew_slo=4.0, health_backlog_slo=0.0,
                 health_anomaly_z=0.0)
