"""Single source of truth for metric-key prefixes and names.

Every subsystem that emits into the trainer's log stream owns a key
family — ``transport_*`` (LearnerServer counters), ``pipeline_*``
(ingest TimeSplit + pipeline counters), ``serve_*`` (central
inference), ``device_*`` (the fused Anakin path), ``shard*`` (sharded
learner) — and the families grew by hand across PRs 5-11. This module
declares the prefixes (imported by the emitters, so a typo'd prefix
is an ImportError, not a silent new family) and the registry of every
statically-reachable key in each family. ``analysis/drift.py``
cross-checks the registry against the tree: a key emitted but not
declared, declared but never emitted, or colliding with a config-knob
name is a finding.

Dynamic key segments (runtime-formatted shard indices) use ``*``:
``shard*_conns`` covers ``shard0_conns``..``shardN_conns``. The
registry is the union of statically-reachable keys — where one
module binds several TimeSplit prefixes to one attribute name, the
checker (and therefore this registry) takes the cartesian closure.

Pure stdlib, no imports: safe to import from scripts/check.py and
bench subprocesses without dragging in jax.
"""

from __future__ import annotations

# --- family prefixes (import these; never inline the strings) --------
TRANSPORT = "transport_"
PIPELINE = "pipeline_"
SERVE = "serve_"
DEVICE = "device_"
SHARD = "shard"          # shard{N}_* dynamic keys + shard_* statics
REPLAY = "replay_"       # prioritized replay tier (distributed/replay.py)
ELASTIC = "elastic_"     # live membership / resharding (distributed/elastic.py)
AUTOSCALER = "autoscaler_"   # fleet-scale policy (distributed/elastic.py)
DELIVERY = "delivery_"   # continuous delivery (distributed/delivery.py)
PROMO = "promo_"         # promotion latency (LatencyStats.summary prefix)
TENANT = "tenant"        # tenant{N}_* dynamic keys + tenant_* statics
                         # (distributed/tenancy.py admission + registry)
SERVE_ACT = SERVE + "act_"   # LatencyStats.summary prefix (serving tier)
REPLAY_SAMPLE = REPLAY + "sample_"  # LatencyStats.summary prefix (draws)
REPLAY_PIPELINE = REPLAY + "pipeline_"  # learner-side replay pipeline
                                        # (data/replay_pipeline.py)

FAMILY_PREFIXES = (
    TRANSPORT, PIPELINE, SERVE, DEVICE, SHARD, REPLAY, ELASTIC,
    AUTOSCALER, REPLAY_PIPELINE, DELIVERY, PROMO, TENANT,
)

# --- registry: family key -> one-line provenance ---------------------
# ``*`` covers runtime-formatted segments (shard indices). Keep keys
# grouped by emitter; analysis/drift.py fails the gate on any key
# used-but-undeclared (DRIFT002) or declared-but-unused (DRIFT003).
METRIC_NAMES: dict = {
    # -- transport_*: LearnerServer.metrics() (distributed/transport.py)
    TRANSPORT + "actors_connected": "live registry connections",
    TRANSPORT + "accepts": "lifetime accepted connections",
    TRANSPORT + "disconnects": "lost peers (incl. idle recycles)",
    TRANSPORT + "graceful_closes": "KIND_CLOSE goodbyes received",
    TRANSPORT + "idle_recycled": "connections recycled for silence",
    TRANSPORT + "frames_in": "frames ingested (all kinds)",
    TRANSPORT + "mb_in": "payload megabytes ingested",
    TRANSPORT + "trajectories": "trajectory frames ingested",
    TRANSPORT + "rejected": "trajectories rejected by the validator",
    TRANSPORT + "traj_frames": "plain trajectory frames",
    TRANSPORT + "traj_coded_frames": "coded trajectory frames",
    TRANSPORT + "traj_mb_in": "trajectory payload MB (all frames)",
    TRANSPORT + "traj_coded_mb_in": "coded trajectory payload MB",
    TRANSPORT + "obs_reqs": "serving-tier observation requests in",
    TRANSPORT + "obs_mb_in": "observation request payload MB",
    TRANSPORT + "act_resps": "serving-tier action replies out",
    TRANSPORT + "sample_reqs": "replay-tier sample requests in",
    TRANSPORT + "sample_batches": "replay-tier prioritized batches out",
    TRANSPORT + "sample_mb_out": "replay-tier batch payload MB out",
    TRANSPORT + "prio_updates": "replay-tier priority updates received",
    TRANSPORT + "member_reqs": "membership-view requests answered",
    TRANSPORT + "reshard_notices": "elastic replan notices received",
    TRANSPORT + "candidate_polls": "evaluator candidate polls answered",
    TRANSPORT + "verdicts_in": "signed promotion verdicts received",
    TRANSPORT + "param_staleness_mean": "mean publishes-behind at fetch",
    TRANSPORT + "pings": "heartbeat probes received",
    TRANSPORT + "hellos": "identity announcements received",
    TRANSPORT + "checksum_failures": "payload CRC mismatches",
    TRANSPORT + "shed_frames": "TRAJ frames shed at ingress by the "
                               "tenant admission handler (ACKed, "
                               "never decoded)",
    TRANSPORT + "handoffs_sent": "KIND_HANDOFF frames to standbys",
    TRANSPORT + "io_threads": "threads serving receives (reactor: 1 "
                              "loop regardless of fleet size; "
                              "threads mode: accept + per-conn)",
    TRANSPORT + "reactor_wakeups": "event-loop readiness passes "
                                   "(reactor mode only)",
    TRANSPORT + "send_stalls": "connections recycled because a peer "
                               "stopped draining its buffered sends "
                               "(reactor mode only)",
    TRANSPORT + "mb_out": "megabytes sent (all frames)",
    TRANSPORT + "param_sends": "param fetches served",
    TRANSPORT + "param_delta_sends": "param fetches served as deltas",
    TRANSPORT + "param_mb_out": "param payload megabytes out",
    TRANSPORT + "notifies_sent": "publish notifies delivered",
    # -- pipeline_*: ingest TimeSplit + LearnerPipeline counters
    # (data/pipeline.py, algos/impala.py, distributed/sharding.py)
    PIPELINE + "queue_wait_s": "waiting on the trajectory queue",
    PIPELINE + "assemble_s": "batch assembly into arena slots",
    PIPELINE + "transfer_s": "host->device transfer",
    PIPELINE + "compute_s": "learner-step compute (serial loop)",
    PIPELINE + "stall_s": "learner blocked on an empty pipeline",
    PIPELINE + "slot_wait_s": "waiting on a free arena slot",
    PIPELINE + "decode_s": "coded-frame decode into slots",
    PIPELINE + "collect_s": "device self-play batch collection",
    PIPELINE + "barrier_wait_s": "sharded stitch/barrier wait",
    PIPELINE + "overlap_frac": "ingest hidden behind compute (0-1)",
    PIPELINE + "batches": "batches staged",
    PIPELINE + "depth": "ready-queue depth",
    PIPELINE + "coded_parts": "coded trajectory parts decoded",
    PIPELINE + "decode_errors": "undecodable coded trajectories",
    PIPELINE + "decode_rejects": "post-decode validator rejects",
    PIPELINE + "shard_batches_min": "min per-shard staged batches",
    # -- serve_*: InferenceServer.metrics() (distributed/serving.py)
    # + the serving bench ledger columns (scripts/serve_bench.py)
    SERVE + "sweep": "BENCH_SERVE fleet-sweep payload section "
                     "(reactor vs threads receive drivers; "
                     "scripts/serve_bench.py sweep_leg)",
    SERVE + "requests": "observation requests submitted",
    SERVE + "dup_replays": "idempotent replays of cached replies",
    SERVE + "seq_resets": "per-actor sequence-lane resets",
    SERVE + "rejected": "malformed/out-of-window requests",
    SERVE + "batches": "act() dispatches",
    SERVE + "batch_mean": "mean requests per act() dispatch",
    SERVE + "segments": "server-side rollout segments completed",
    SERVE + "reply_failures": "replies to already-gone connections",
    SERVE + "param_swaps": "in-process serving weight swaps",
    SERVE + "lanes": "live per-actor lanes",
    SERVE + "lane_retires": "lanes retired on actor goodbyes "
                            "(elastic leave)",
    SERVE + "canary_fraction": "configured canary lane fraction "
                               "(0 = no candidate staged)",
    SERVE + "canary_lanes": "lanes currently routed to the candidate",
    SERVE + "canary_requests": "requests served BY the candidate",
    SERVE + "canary_batches": "candidate-params act() dispatches",
    SERVE + "candidate_clears": "staged candidates cleared "
                                "(reject/rollback)",
    SERVE + "shadow_batches": "shadow-scored act() dispatches",
    SERVE + "shadow_divergence": "mean live-vs-candidate action "
                                 "divergence under shadow",
    SERVE + "tenants": "distinct tenants with live serving lanes",
    SERVE + "policy_group_ticks": "batching ticks that dispatched "
                                  "more than one per-policy group",
    SERVE_ACT + "count": "act latency samples",
    SERVE_ACT + "mean_ms": "act latency mean",
    SERVE_ACT + "p50_ms": "act latency p50",
    SERVE_ACT + "p99_ms": "act latency p99",
    SERVE_ACT + "max_ms": "act latency max",
    SERVE + "p50_ms": "serve bench ledger: per-fleet p50 column",
    SERVE + "p99_ms": "serve bench ledger: per-fleet p99 column",
    # -- device_*: fused Anakin path TimeSplit (algos/impala.py,
    # data/pipeline.py DeviceBatchSource) + bench.py device leg
    DEVICE + "step_s": "fused-iteration dispatch wall time",
    DEVICE + "collect_s": "device self-play collection",
    DEVICE + "batches": "device-collected batches",
    DEVICE + "queue_wait_s": "device source: staging wait",
    DEVICE + "assemble_s": "device source: assembly",
    DEVICE + "transfer_s": "device source: transfer",
    DEVICE + "stall_s": "device source: learner stall",
    DEVICE + "slot_wait_s": "device source: slot wait",
    DEVICE + "decode_s": "device source: decode",
    DEVICE + "steps_per_sec": "bench device leg: env-steps/sec",
    DEVICE + "step_share": "bench device leg: step_s share of wall",
    DEVICE + "vs_pipelined": "bench device leg: speedup vs pipelined",
    DEVICE + "vs_serial": "bench device leg: speedup vs serial",
    DEVICE + "kind": "bench.py result stamp: "
                     "jax.devices()[0].device_kind",
    # -- replay_*: prioritized replay tier (distributed/replay.py
    # shard + client-group counters, algos/offpolicy_distributed.py
    # learner loop, plus the pre-existing fused-path ring gauge)
    REPLAY + "size": "rows resident in a shard's ring (also the "
                     "fused path's HBM ring gauge)",
    REPLAY + "inserted": "transitions ingested (shard / aggregate)",
    REPLAY + "samples_served": "prioritized batches a shard served",
    REPLAY + "sample_rows": "rows a shard served across batches",
    REPLAY + "prio_applied": "priority updates applied to live rows",
    REPLAY + "prio_stale": "priority updates dropped (row overwritten)",
    REPLAY + "layout_rejects": "transition frames off the pinned layout",
    REPLAY + "draws": "learner draws served across shards",
    REPLAY + "refills": "draws answered meta-only (shard refilling)",
    REPLAY + "sample_failovers": "draws failed over past a dead shard",
    REPLAY + "prio_failures": "priority updates lost to transport",
    REPLAY + "updates": "gradient updates on wire-sourced batches",
    REPLAY + "server_restarts": "replay-server processes respawned",
    REPLAY + "actor_respawns": "env-stepper actor processes respawned",
    REPLAY + "batch_rejects": "sampled batches off the expected layout",
    REPLAY + "shards": "replay shard count (log attribution)",
    REPLAY + "ingest_tps": "replay ingest throughput (autoscaler "
                           "low-watermark input; bench ledger column)",
    # -- replay_* durability / failover (PR 14: ring snapshots,
    # learner checkpoint/resume, warm-standby fencing)
    REPLAY + "snapshots": "ring snapshots a shard wrote to disk",
    REPLAY + "snapshot_age_s": "seconds since a shard's last snapshot "
                               "(-1 = never)",
    REPLAY + "restore_frac": "ring-restore load progress (1.0 = "
                             "serving)",
    REPLAY + "restored_rows": "rows a respawned shard restored from "
                              "its snapshot chain",
    REPLAY + "drop_restoring": "ingest frames dropped during a ring "
                               "restore",
    REPLAY + "prio_fenced": "priority updates dropped from a deposed "
                            "learner's reign",
    REPLAY + "ckpt_saves": "learner checkpoints written this run",
    REPLAY + "fence_epoch": "the learner's fencing reign (bumps per "
                            "takeover/resume)",
    REPLAY + "shards_restoring": "shards currently loading a ring "
                                 "snapshot",
    REPLAY + "reshards": "live ring re-deals applied (autoscale_"
                         "reshard topology changes)",
    # -- replay_pipeline_*: learner-side replay pipeline (PR 17:
    # data/replay_pipeline.py TimeSplit buckets + counters, surfaced
    # through the off-policy learner loop's log tick)
    REPLAY_PIPELINE + "sample_wait_s": "prefetch workers blocked in "
                                       "sample RPCs",
    REPLAY_PIPELINE + "slot_wait_s": "workers waiting on a free arena "
                                     "slot (token-gated reuse)",
    REPLAY_PIPELINE + "assemble_s": "decode into arena slots",
    REPLAY_PIPELINE + "transfer_s": "host->device transfer of staged "
                                    "batches",
    REPLAY_PIPELINE + "stall_s": "learner blocked on an empty "
                                 "prefetch window",
    REPLAY_PIPELINE + "batches": "batches staged through the window",
    REPLAY_PIPELINE + "depth": "configured prefetch window depth",
    REPLAY_PIPELINE + "inflight": "draws issued but not yet consumed",
    REPLAY_PIPELINE + "rejects": "staged batches off the pinned "
                                 "layout",
    REPLAY_PIPELINE + "reissues": "draws reissued after an "
                                  "interrupted/faulted in-flight draw",
    REPLAY_PIPELINE + "prio_frames": "priority write-back frames sent",
    REPLAY_PIPELINE + "prio_entries": "batch write-backs carried "
                                      "across frames",
    REPLAY_PIPELINE + "prio_frames_coalesced": "frames that coalesced "
                                               "more than one batch",
    REPLAY_PIPELINE + "overlap_frac": "staging hidden behind update "
                                      "compute (0-1)",
    REPLAY_PIPELINE + "sample_wait_share": "share of wall time the "
                                           "learner waited on the "
                                           "window",
    # -- elastic_*: live membership + epoch-fenced resharding
    # (distributed/elastic.py MembershipView / ReshardCoordinator,
    # surfaced through the off-policy learner loop)
    ELASTIC + "fleet": "live actors in the membership view",
    ELASTIC + "joins": "actors that joined the fleet at runtime",
    ELASTIC + "leaves": "actors that left (or were lost) at runtime",
    ELASTIC + "rejoins": "actors that rejoined under a newer "
                         "generation",
    ELASTIC + "membership_version": "membership view version (bumps "
                                    "per fleet change)",
    ELASTIC + "reshards": "epoch-fenced reshard events completed",
    ELASTIC + "moved_actors": "actors moved by the last rebalance",
    ELASTIC + "plan_epoch": "fencing epoch of the committed shard "
                            "plan",
    # -- autoscaler_*: threshold policy decisions
    # (distributed/elastic.py Autoscaler)
    AUTOSCALER + "decisions": "policy evaluations taken",
    AUTOSCALER + "scale_ups": "scale-up decisions issued",
    AUTOSCALER + "scale_downs": "scale-down decisions issued",
    AUTOSCALER + "holds": "evaluations that held the fleet size",
    AUTOSCALER + "target_actors": "current fleet-size target",
    AUTOSCALER + "cooldown_active": "1 while the post-decision "
                                    "cooldown holds",
    REPLAY_SAMPLE + "count": "sample-draw latency samples",
    REPLAY_SAMPLE + "mean_ms": "sample-draw latency mean",
    REPLAY_SAMPLE + "p50_ms": "sample-draw latency p50",
    REPLAY_SAMPLE + "p99_ms": "sample-draw latency p99",
    REPLAY_SAMPLE + "max_ms": "sample-draw latency max",
    # -- delivery_*: continuous-delivery controller + policy store
    # (distributed/delivery.py, surfaced through the trainers' log
    # ticks and scripts/delivery_bench.py)
    DELIVERY + "candidates": "candidate snapshots submitted",
    DELIVERY + "promotions": "candidates promoted to the fleet "
                             "(incl. the bootstrap auto-promote)",
    DELIVERY + "rejections": "candidates rejected by the eval gate",
    DELIVERY + "quarantines": "candidates quarantined on verdict "
                              "timeout (evaluator dead)",
    DELIVERY + "rollbacks": "one-knob epoch-bump rollbacks taken",
    DELIVERY + "bad_signatures": "verdicts dropped on signature "
                                 "verification failure",
    DELIVERY + "stale_verdicts": "verdicts for no-longer-pending "
                                 "candidates dropped",
    DELIVERY + "store_size": "candidates resident in the policy store",
    DELIVERY + "store_evictions": "settled candidates evicted from "
                                  "the keep window",
    DELIVERY + "pending": "candidates awaiting a verdict",
    DELIVERY + "verdict_quorum": "signed verdicts required to settle "
                                 "a candidate (delivery_quorum knob)",
    DELIVERY + "verdict_votes": "quorum votes received (lifetime)",
    DELIVERY + "votes_pending": "partial-quorum votes held on "
                                "unsettled candidates",
    # -- tenant_* / tenant{N}_*: multi-tenant admission + registry
    # (distributed/tenancy.py TenantAdmission / PolicyRegistry,
    # per-tenant serving counters in distributed/serving.py, and the
    # noisy-neighbor bench ledger in scripts/tenancy_bench.py)
    TENANT + "_count": "tenants with admission-counter activity",
    TENANT + "_frames_admitted": "frames admitted (all tenants)",
    TENANT + "_frames_shed": "frames shed over budget (all tenants)",
    TENANT + "_mb_shed": "payload MB shed over budget (all tenants)",
    TENANT + "*_frames_admitted": "per-tenant frames admitted",
    TENANT + "*_frames_shed": "per-tenant frames shed over budget",
    TENANT + "*_mb_in": "per-tenant payload MB offered at ingress",
    TENANT + "*_mb_shed": "per-tenant payload MB shed over budget",
    TENANT + "*_budget_mb_s": "per-tenant token-bucket budget "
                              "(0 = unmetered)",
    TENANT + "*_serve_requests": "per-tenant serving-tier requests",
    TENANT + "_registry_tenants": "tenants with registry ledgers",
    TENANT + "_registry_policies": "(tenant, policy) stores resident",
    TENANT + "_registry_events": "ledger events recorded (lifetime)",
    # -- promo_*: candidate-submitted -> promoted-and-serving latency
    # (DeliveryController's LatencyStats.summary)
    PROMO + "count": "promotion latency samples",
    PROMO + "mean_ms": "promotion latency mean",
    PROMO + "p50_ms": "promotion latency p50 (the BENCH_PROMOTION "
                      "headline)",
    PROMO + "p99_ms": "promotion latency p99",
    PROMO + "max_ms": "promotion latency max",
    # -- shard*: sharded-learner log attribution (algos/impala.py)
    # + the shard bench ledger (scripts/shard_bench.py)
    SHARD + "_count": "topology echo: shard count (log attribution)",
    SHARD + "_id": "topology echo: this host's shard id",
    SHARD + "*_conns": "per-shard live actor connections",
    SHARD + "*_foreign_peers": "per-shard peers outside the slice",
    SHARD + "*_trajectories": "per-shard trajectories ingested",
    SHARD + "s": "shard bench ledger: shard counts column",
}
