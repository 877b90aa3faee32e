"""Canonical metric / span / event / fault-site names — ONE place.

Every counter, gauge, histogram, wall-clock timing label, span, event,
and fault-injection site the framework records is declared here, with a
one-line description. `graftlint` (mmlspark_tpu/analysis) enforces the
contract in both directions: package call sites must use names declared
here (as the constants below — a raw literal that is not canonical is
flagged, with typo suggestions), and every declared name must appear in
the docs/observability.md name table.

Conventions:

- Names are dotted, `subsystem.signal[.detail]`, lowercase.
- Patterned names carry `{placeholder}` segments (e.g.
  ``train.step{step}``); the helpers below render them. Keep the
  placeholder text meaningful — it is the documentation.
- FAULT SITES ARE THE EXCEPTION to the use-the-constant rule: the
  literal at a `perturb("...")`/`fire("...")` call site is what the
  analyzer cross-references against chaos-test schedules
  (`fault-site-unknown` / `fault-site-untested`), so fire sites keep
  their strings inline and this registry validates them.
- This module is pure stdlib data: importable from every layer (and
  executed standalone by the analyzer) with zero dependency cost.

Metric-family names (counters/gauges/histograms/timings) share the
`MetricsRegistry.snapshot()` namespace — never reuse one name across two
of those kinds (`metric-kind-collision` enforces it).
"""
from __future__ import annotations

# --------------------------------------------------------------- counters
SERVING_SHED_REQUESTS = "serving.shed_requests"
SERVING_REQUEST_TOTAL = "serving.request.total"
SERVING_REQUEST_ERRORS = "serving.request.errors"
TELEMETRY_POLL_SAMPLES = "telemetry.poll.samples"
TELEMETRY_POLL_ERRORS = "telemetry.poll.errors"
SERVING_WORKER_RESTARTS = "serving.worker_restarts"
SERVING_REPLAYED_EPOCHS = "serving.replayed_epochs"
SERVING_SIGNAL_DRAINS = "serving.signal_drains"
SERVING_PLAN_HITS = "serving.plan.hits"
SERVING_PLAN_MISSES = "serving.plan.misses"
CHECKPOINT_SAVE_COUNT = "checkpoint.save.count"
CHECKPOINT_SAVE_BYTES = "checkpoint.save.bytes"
CHECKPOINT_CORRUPT_SKIPPED = "checkpoint.corrupt_skipped"
CHECKPOINT_DIGEST_MISMATCH = "checkpoint.digest_mismatch"
CHECKPOINT_WRITE_COALESCED = "checkpoint.write.coalesced"
CHECKPOINT_WRITE_ERRORS = "checkpoint.write.errors"
CHECKPOINT_FINALIZE_ERRORS = "checkpoint.finalize_errors"
TRAIN_RESUMES = "train.resumes"
TRAIN_STEP_RESTARTS = "train.step_restarts"
TRAIN_STEP_TIMEOUTS = "train.step_timeouts"
TRAIN_STEP_RETRIES = "train.step_retries"
TRAIN_PREEMPTED = "train.preempted"
TRAIN_PREEMPT_SIGNALS = "train.preempt_signals"
CLUSTER_REJOINS = "cluster.rejoins"
CLUSTER_HEARTBEAT_ERRORS = "cluster.heartbeat_errors"
CLUSTER_RENDEZVOUS_RETRIES = "cluster.rendezvous_retries"
CLUSTER_FENCE_REJECTS = "cluster.fence_rejects"
CLUSTER_HEARTBEAT_TMP_SWEPT = "cluster.heartbeat_tmp_swept"
ELASTIC_MANIFEST_COMMITS = "elastic.manifest.commits"
ELASTIC_MANIFEST_REJECTED = "elastic.manifest.rejected"
ELASTIC_SHRINKS = "elastic.shrinks"
ELASTIC_RESUMES = "elastic.resumes"
REGISTRY_REPORT_RETRIES = "registry.report_retries"
HTTP_RETRIES = "http.retries"
RETRY_RETRIES = "retry.retries"
DATA_WORKER_FAILURES = "data.worker_failures"
DATA_PREFETCH_ITEMS = "data.prefetch.items"
DATA_PREFETCH_STALLS = "data.prefetch.stalls"
DATA_PREFETCH_FULL = "data.prefetch.full"
PLAN_COMPILES = "plan.compiles"
PLAN_RECOMPILES = "plan.recompiles"
PLAN_COLLECTIVE_OPS = "plan.collective_ops"
PLAN_COLLECTIVE_BYTES = "plan.collective_bytes"
SERVING_PLAN_EVICTIONS = "serving.plan.evictions"
TELEMETRY_BUNDLE_DUMPS = "telemetry.bundle.dumps"
TELEMETRY_BUNDLE_SUPPRESSED = "telemetry.bundle.suppressed"
TELEMETRY_PROFILE_CAPTURES = "telemetry.profile.captures"
TELEMETRY_PROFILE_SUPPRESSED = "telemetry.profile.suppressed"
TELEMETRY_PROFILE_STAMP_ERRORS = "telemetry.profile.stamp_errors"
LM_STEP_COMPILES = "lm.step.compiles"
LM_STEP_SLOW = "lm.step.slow"
LM_STEP_LOST_SECONDS = "lm.step.lost_seconds"
FLASH_TILES_COMPUTED = "flash.tiles.computed"
FLASH_TILES_SKIPPED = "flash.tiles.skipped"
MOE_PAIRS_ROUTED = "moe.pairs.routed"
MOE_PAIRS_HELD = "moe.pairs.held"
GDN_SCAN_ROUTE_PALLAS = "gdn.scan.route.pallas"
GDN_SCAN_ROUTE_XLA = "gdn.scan.route.xla"
GDN_MIXER_ROUTE_PALLAS = "gdn.mixer.route.pallas"
GDN_MIXER_ROUTE_XLA = "gdn.mixer.route.xla"
LM_REMAT_KEEP_FLASH = "lm.remat.keep.flash"
LM_REMAT_KEEP_ROUTING = "lm.remat.keep.routing"
SSM_SCAN_ROUTE_PALLAS = "ssm.scan.route.pallas"
SSM_SCAN_ROUTE_XLA = "ssm.scan.route.xla"
LM_SHARED_READERS = "lm.shared.readers"
MOE_EXPERTS_ROUTE_PALLAS = "moe.experts.route.pallas"
MOE_EXPERTS_ROUTE_XLA = "moe.experts.route.xla"
EMBED_GRAD_ROUTE_PALLAS = "embed.grad.route.pallas"
EMBED_GRAD_ROUTE_XLA = "embed.grad.route.xla"
TELEMETRY_WATCH_TRIPS = "telemetry.watch.trips"
QUALITY_LABELS_JOINED = "quality.labels.joined"
QUALITY_LABELS_LATE = "quality.labels.late"
QUALITY_LABELS_DUP = "quality.labels.dup"
QUALITY_LABELS_DROPPED = "quality.labels.dropped"
QUALITY_JOIN_SUBSCRIBER_ERRORS = "quality.join.subscriber_errors"
QUALITY_SKETCH_ROWS = "quality.sketch.rows"
ONLINE_FEED_PAIRS = "online.feed.pairs"
ONLINE_FEED_DROPPED = "online.feed.dropped"
ONLINE_LEARNER_UPDATES = "online.learner.updates"
ONLINE_TRIPS = "online.trips"
ONLINE_REFITS = "online.refits"
ONLINE_REFIT_RETRIES = "online.refit_retries"
ONLINE_PROMOTIONS = "online.promotions"
ONLINE_ROLLBACKS = "online.rollbacks"
SERVING_MODEL_SWAPS = "serving.model.swaps"
SERVING_MODEL_SWAP_ERRORS = "serving.model.swap_errors"
REGISTRY_EVICTIONS = "registry.evictions"
CONTROL_ROLLOUT_STEPS = "control.rollout.steps"
CONTROL_ROLLOUT_PROMOTIONS = "control.rollout.promotions"
CONTROL_ROLLOUT_ROLLBACKS = "control.rollout.rollbacks"
CONTROL_ROLLOUT_ROLLBACK_RETRIES = "control.rollout.rollback_retries"
CONTROL_ROLLOUT_POLL_ERRORS = "control.rollout.poll_errors"
CONTROL_ADMISSION_SHED = "control.admission.shed"
CONTROL_ROUTER_UPDATES = "control.router.updates"
CONTROL_SCALER_SPAWNS = "control.scaler.spawns"
CONTROL_SCALER_DRAINS = "control.scaler.drains"
WORKLOADS_IFOREST_TREES = "workloads.iforest.trees"
WORKLOADS_SAR_RECOMMEND_ROWS = "workloads.sar.recommend.rows"
WORKLOADS_SAR_UNKNOWN_USERS = "workloads.sar.unknown_users"

COUNTERS = {
    SERVING_SHED_REQUESTS: "requests answered 503 (drain or max_queue "
                           "load shedding)",
    SERVING_REQUEST_TOTAL: "requests accepted at ingress (exposition "
                           "self-scrapes excluded) — SLO denominators",
    SERVING_REQUEST_ERRORS: "requests answered 5xx (shed, timeout, model "
                            "failure) — SLO error-budget numerators",
    TELEMETRY_POLL_SAMPLES: "fleet snapshots captured by TelemetryPoller",
    TELEMETRY_POLL_ERRORS: "TelemetryPoller scrape rounds that failed "
                           "(absorbed; last good sample stands)",
    SERVING_WORKER_RESTARTS: "partition worker threads restarted by the "
                             "watchdog",
    SERVING_REPLAYED_EPOCHS: "uncommitted epochs replayed after a worker "
                             "death/failure",
    SERVING_SIGNAL_DRAINS: "SIGTERM/SIGINT graceful drains taken",
    SERVING_PLAN_HITS: "compiled-plan cache hits (fingerprint, bucket)",
    SERVING_PLAN_MISSES: "compiled-plan cache misses (one compile each)",
    CHECKPOINT_SAVE_COUNT: "checkpoints written",
    CHECKPOINT_SAVE_BYTES: "bytes written across checkpoint payloads",
    CHECKPOINT_CORRUPT_SKIPPED: "truncated/unreadable checkpoint steps "
                                "skipped on restore",
    CHECKPOINT_DIGEST_MISMATCH: "checkpoint steps failing SHA-256 verify "
                                "on restore",
    CHECKPOINT_WRITE_COALESCED: "async snapshots dropped latest-wins "
                                "under backpressure",
    CHECKPOINT_WRITE_ERRORS: "async checkpoint writes that failed "
                             "(absorbed)",
    CHECKPOINT_FINALIZE_ERRORS: "final-checkpoint failures during "
                                "supervisor finalize",
    TRAIN_RESUMES: "supervisor runs resumed from a checkpoint",
    TRAIN_STEP_RESTARTS: "step-loop restarts from the in-memory snapshot",
    TRAIN_STEP_TIMEOUTS: "steps killed by the step_timeout watchdog",
    TRAIN_STEP_RETRIES: "step retry attempts under the restart "
                        "RetryPolicy",
    TRAIN_PREEMPTED: "runs ended by preemption (final checkpoint taken)",
    TRAIN_PREEMPT_SIGNALS: "SIGTERM/SIGINT deliveries observed mid-run",
    CLUSTER_REJOINS: "processes that found their own prior heartbeat at "
                     "startup",
    CLUSTER_HEARTBEAT_ERRORS: "heartbeat writes that failed (counted, "
                              "never fatal)",
    CLUSTER_RENDEZVOUS_RETRIES: "jax.distributed rendezvous connection "
                                "retries",
    CLUSTER_FENCE_REJECTS: "heartbeat writes rejected by the epoch fence "
                           "(a zombie host beating after its death "
                           "verdict; the row is never written)",
    CLUSTER_HEARTBEAT_TMP_SWEPT: "stale heartbeat .tmp files (a crash "
                                 "between tmp-write and os.replace) swept "
                                 "at Heartbeat startup",
    ELASTIC_MANIFEST_COMMITS: "fleet checkpoint manifests committed by "
                              "the leader (every member shard landed and "
                              "digest-recorded)",
    ELASTIC_MANIFEST_REJECTED: "fleet manifests refused on restore "
                               "(torn JSON, missing member shard, or "
                               "member digest mismatch) — restore falls "
                               "back to the last fully-committed step",
    ELASTIC_SHRINKS: "shrink plans derived after a death verdict "
                     "(survivor set + chunk restage computed)",
    ELASTIC_RESUMES: "shrink-resumes taken from a committed fleet "
                     "manifest",
    REGISTRY_REPORT_RETRIES: "worker->registry registration retries",
    HTTP_RETRIES: "HTTP handler retry attempts (io/http.py)",
    RETRY_RETRIES: "generic utils.retry attempts",
    DATA_WORKER_FAILURES: "ingest pool chunk failures (first failing "
                          "chunk raises)",
    DATA_PREFETCH_ITEMS: "batches fed through DevicePrefetcher",
    DATA_PREFETCH_STALLS: "consumer arrived at an empty prefetch queue",
    DATA_PREFETCH_FULL: "feeder found the prefetch queue full (device is "
                        "the bottleneck)",
    PLAN_COMPILES: "plan builds / AOT jit compiles recorded "
                   "(telemetry.perf compile log)",
    PLAN_RECOMPILES: "a (fingerprint, shape bucket) compiled AGAIN — "
                     "steady-state serving pins this to zero",
    PLAN_COLLECTIVE_OPS: "collective instructions (all-reduce, "
                         "collective-permute, ...) in recorded executables",
    PLAN_COLLECTIVE_BYTES: "per-device collective payload bytes in "
                           "recorded executables (COMM_TRAFFIC account)",
    SERVING_PLAN_EVICTIONS: "compiled plans evicted (LRU) from the "
                            "bounded plan cache",
    TELEMETRY_BUNDLE_DUMPS: "flight-recorder debug bundles written",
    TELEMETRY_BUNDLE_SUPPRESSED: "flight-recorder triggers suppressed by "
                                 "the rate limit",
    TELEMETRY_PROFILE_CAPTURES: "device-profile captures written "
                                "(ProfileSession)",
    TELEMETRY_PROFILE_SUPPRESSED: "profile triggers suppressed by the "
                                  "capture rate limit",
    TELEMETRY_PROFILE_STAMP_ERRORS: "trace_context.json stamps that "
                                    "failed (capture kept, stamp lost)",
    TELEMETRY_WATCH_TRIPS: "telemetry watcher rule trip TRANSITIONS "
                           "(threshold or median-shift)",
    LM_STEP_COMPILES: "compilations of PipelinedLMTrainer's own step "
                      "program, seen during lm.step.dispatch (a new shape, "
                      "or the second step's donated layouts)",
    LM_STEP_SLOW: "steps of PipelinedLMTrainer that telemetry.profiler."
                  "slow_steps called slow when they were recorded: no "
                  "compile, and a period (gap + h2d + dispatch + wait) "
                  "over 1.03 of the median period of the step records "
                  "then in the ring",
    LM_STEP_LOST_SECONDS: "seconds those slow steps took beyond that "
                          "median period (a float; which phase held them "
                          "and what the host did meanwhile is in "
                          "telemetry.profiler.step_records)",
    FLASH_TILES_COMPUTED: "sub-tiles a traced flash kernel call can execute, "
                          "per (batch, head), recorded at trace time: the "
                          "lower triangle of each diagonal cell plus every "
                          "cell below it where the in-cell causal schedule "
                          "engages, else one per grid cell",
    FLASH_TILES_SKIPPED: "sub-tiles above the diagonal that the in-cell "
                         "causal schedule of a traced flash kernel call "
                         "leaves out (0 for non-causal, cross-attention and "
                         "traced-offset ring calls)",
    MOE_PAIRS_ROUTED: "(token, expert) pairs the routers of the expert "
                      "layers made, summed over the layers of the steps "
                      "PipelinedLMTrainer.step ran (read from the numbers "
                      "the step program returns with its loss)",
    MOE_PAIRS_HELD: "of moe.pairs.routed, the pairs routed to an expert "
                    "this chip holds, which are the ones it computed",
    GDN_SCAN_ROUTE_PALLAS: "calls of the gated delta rule traced down the "
                           "Pallas kernels (gdn_fwd, gdn_bwd): a TPU and "
                           "shapes that fit them (ops/gated_delta.py), or "
                           "a test's own interpret-mode call; counted at "
                           "trace time, once a call site",
    GDN_SCAN_ROUTE_XLA: "calls of the gated delta rule traced down its XLA "
                        "form: off the TPU, or head sizes that are no "
                        "multiple of 128, or another chunk than 64 (never "
                        "silent)",
    GDN_MIXER_ROUTE_PALLAS: "calls of the Gated-DeltaNet mixer's two fused "
                            "passes (ops/gdn_mixer.py: gdn_prepare, "
                            "gdn_finish) traced down the Pallas kernels "
                            "gdn_prep_fwd / gdn_prep_bwd and gdn_post_fwd "
                            "/ gdn_post_bwd: a TPU, head sizes in "
                            "multiples of 128, bfloat16 or float32, or a "
                            "test's own interpret-mode call; counted at "
                            "trace time, once a pass and call site",
    GDN_MIXER_ROUTE_XLA: "calls of the same two passes traced down their "
                         "plain jnp form on the slab: off the TPU, or head "
                         "sizes that are no multiple of 128 (never silent)",
    SSM_SCAN_ROUTE_PALLAS: "calls of the selective state-space scan traced "
                           "down the Pallas kernels (ssm_fwd, ssm_bwd): a "
                           "TPU, channels in multiples of 128 and states "
                           "of 8 (ops/selective_scan.py), or a test's own "
                           "interpret-mode call; counted at trace time, "
                           "once a call site",
    SSM_SCAN_ROUTE_XLA: "calls of the selective scan traced down its XLA "
                        "form (a lax.scan over chunks): off the TPU, or "
                        "shapes the kernels do not fit (never silent)",
    MOE_EXPERTS_ROUTE_PALLAS: "expert layers whose tile loop was traced "
                              "down the Pallas kernels (moe_fwd, moe_bwd: "
                              "ops/moe_experts.py): a TPU, model and "
                              "expert widths in multiples of 128, "
                              "bfloat16 or float32, whole tiles of pairs "
                              "and a sorted order SMEM holds; counted at "
                              "trace time, once a call of moe._experts",
    MOE_EXPERTS_ROUTE_XLA: "expert layers whose tile loop was traced as "
                           "the XLA while loop (models/dnn/moe.py): off "
                           "the TPU, or shapes the kernels do not fit "
                           "(never silent)",
    EMBED_GRAD_ROUTE_PALLAS: "embedding lookups whose gradient was traced "
                             "down the Pallas kernel embed_grad "
                             "(ops/embedding.py: one sort of the ids, a "
                             "one-hot sum a vocabulary block in VMEM): a "
                             "TPU, a bfloat16 or float32 table whose width "
                             "is a multiple of 128 and int32 ids, or a "
                             "test's own interpret-mode call; counted at "
                             "trace time, once a call of embedding.lookup",
    EMBED_GRAD_ROUTE_XLA: "embedding lookups whose gradient was traced as "
                          "XLA's scatter-add (the gather's own transpose): "
                          "off the TPU, or tables the kernel does not fit "
                          "(never silent)",
    LM_SHARED_READERS: "sublayers of a traced state-space model "
                       "(models/dnn/ssm_layers.py) that read an array an "
                       "earlier layer made: a Gated Memory Unit reads the "
                       "memory layer's scan output, a cross-attention "
                       "layer the KV layer's keys and values; counted at "
                       "trace time, once a reader of the description "
                       "(a run's period is traced once and counts its "
                       "readers times the run's repetitions)",
    LM_REMAT_KEEP_FLASH: "checkpointed mixer sublayers of the hybrid and "
                         "short-convolution families whose policy keeps a "
                         "flash call's output and row sums (flash.forward "
                         "of REMAT_RESIDUALS below), so the backward pass does not run "
                         "flash_fwd again; counted at trace time, once a "
                         "traced sublayer (a period's layer is traced once "
                         "however many periods the scan runs). 0 under "
                         "remat=False, remat=\"save_attn\" (the mixer is not "
                         "checkpointed), attention=\"dense\" and in the "
                         "dense family",
    LM_REMAT_KEEP_ROUTING: "checkpointed expert sublayers of the same two "
                           "families whose policy keeps the routing "
                           "(moe.routing of REMAT_RESIDUALS below: scores, "
                           "chosen ids and scores, the tile plan), so "
                           "the backward pass runs no top-k and no sort "
                           "again; counted like lm.remat.keep.flash. 0 "
                           "under remat=False and in the dense family",
    QUALITY_LABELS_JOINED: "delayed labels joined to their served "
                           "prediction (streaming evaluation pairs)",
    QUALITY_LABELS_LATE: "out-of-order labels that arrived BEFORE their "
                         "prediction and joined late",
    QUALITY_LABELS_DUP: "duplicate labels for an already-joined request "
                        "id (counted, not re-joined)",
    QUALITY_LABELS_DROPPED: "labels lost to the join: prediction aged "
                            "out of the bounded window, parked-label "
                            "eviction, or injected label loss",
    QUALITY_JOIN_SUBSCRIBER_ERRORS: "on_join subscriber callbacks that "
                                    "raised (absorbed; the join itself "
                                    "is never undone)",
    QUALITY_SKETCH_ROWS: "served rows folded into the live quality "
                         "sketches (head-sampled by request id)",
    ONLINE_FEED_PAIRS: "joined (features, label) pairs buffered by the "
                       "LabelFeed for incremental refits",
    ONLINE_FEED_DROPPED: "joined pairs the LabelFeed lost: features "
                         "evicted before the label joined, or the "
                         "bounded pair buffer overflowed",
    ONLINE_LEARNER_UPDATES: "compiled minibatch updates applied by the "
                            "OnlineLearner (one per padded (rows, k) "
                            "bucket execution)",
    ONLINE_TRIPS: "continuous-learner triggers (drift trip or quality "
                  "floor burn) that started a refit cycle",
    ONLINE_REFITS: "incremental refits that completed and produced a "
                   "candidate ModelVersion",
    ONLINE_REFIT_RETRIES: "refit attempts retried under the continuous "
                          "learner's RetryPolicy (each retry rewinds to "
                          "the pre-refit snapshot first)",
    ONLINE_PROMOTIONS: "online candidates promoted by the rollout gate",
    ONLINE_ROLLBACKS: "online candidates rolled back by the rollout "
                      "gate (learner state rewound to the pre-refit "
                      "snapshot)",
    SERVING_MODEL_SWAPS: "install_model hot-swaps committed (the old "
                         "version's plans drain, never invalidate)",
    SERVING_MODEL_SWAP_ERRORS: "install_model swaps that failed and "
                               "rolled back to the incumbent handle",
    REGISTRY_EVICTIONS: "registry entries evicted because no "
                        "re-registration heartbeat landed within the TTL",
    CONTROL_ROLLOUT_STEPS: "candidate traffic-step installs performed by "
                           "the rollout driver (one per staged fraction)",
    CONTROL_ROLLOUT_PROMOTIONS: "rollouts auto-promoted after a clean "
                                "soak window",
    CONTROL_ROLLOUT_ROLLBACKS: "rollouts auto-rolled-back to the "
                               "incumbent (burn or watch trip)",
    CONTROL_ROLLOUT_ROLLBACK_RETRIES: "rollback install_model attempts "
                                      "retried under the driver's "
                                      "RetryPolicy",
    CONTROL_ROLLOUT_POLL_ERRORS: "rollout-driver fleet scrapes that "
                                 "failed (absorbed; the round is skipped)",
    CONTROL_ADMISSION_SHED: "requests shed 503+Retry-After by burn-aware "
                            "admission (error budget burning, queue "
                            "non-empty)",
    CONTROL_ROUTER_UPDATES: "weighted-router weight table refreshes from "
                            "fleet scrapes",
    CONTROL_SCALER_SPAWNS: "spawn hooks fired by the occupancy-driven "
                           "fleet scaler",
    CONTROL_SCALER_DRAINS: "drain hooks fired by the occupancy-driven "
                           "fleet scaler",
    WORKLOADS_IFOREST_TREES: "isolation trees grown (one supervisor step "
                             "each — the resumable fit cursor's rate)",
    WORKLOADS_SAR_RECOMMEND_ROWS: "user rows answered by the compiled "
                                  "SAR recommend plan (served top-k "
                                  "batches, after bucket-pad trim)",
    WORKLOADS_SAR_UNKNOWN_USERS: "recommend requests for user ids "
                                 "outside the fitted range (answered "
                                 "items=-1/ratings=NaN, the cold-start "
                                 "convention)",
    "data.pool.{mode}_maps": "WorkerPool.map_rows calls per backend "
                             "(process/thread)",
    "gbdt.hist.route.{route}": "histogram kernel-route selections "
                               "(direct/joint/planes/xla), recorded at "
                               "trace time — one per compiled (m, B) "
                               "instantiation",
    "{breaker}.trips": "circuit-breaker trips, one counter per breaker "
                       "name",
}

# ----------------------------------------------------------------- gauges
ANALYSIS_SEMANTIC_CONTRACTS = "analysis.semantic.contracts"
ANALYSIS_SEMANTIC_FINDINGS = "analysis.semantic.findings"
GBDT_HIST_PLAN_BYTES = "gbdt.hist.plan.bytes"
SERVING_QUEUE_DEPTH = "serving.queue_depth"
SERVING_BATCH_OCCUPANCY = "serving.batch.occupancy"
CHECKPOINT_WRITE_PENDING = "checkpoint.write.pending"
TRAIN_RESUME_STEP = "train.resume_step"
CLUSTER_RESUME_EPOCH = "cluster.resume_epoch"
DEVICE_MEM_BYTES_IN_USE = "device.mem.bytes_in_use"
DEVICE_MEM_PEAK_BYTES = "device.mem.peak_bytes"
HOST_RSS_BYTES = "host.rss_bytes"
TRAIN_GOODPUT = "train.goodput"
TRAIN_MFU = "train.mfu"
TRAIN_LOST_SECONDS = "train.lost_seconds"
TRAIN_STRAGGLERS = "train.stragglers"
TELEMETRY_WATCH_TRIPPED = "telemetry.watch.tripped"
QUALITY_DRIFT_MAX = "quality.drift.max"
ONLINE_BUFFER_PAIRS = "online.buffer.pairs"
SERVING_MODEL_VERSION_INFO = "serving.model.version_info"
CANARY_P99_RATIO = "canary.p99.ratio"
CANARY_ERROR_BURN = "canary.error_burn"
CANARY_DRIFT_DELTA = "canary.drift.delta"
CONTROL_ROLLOUT_FRACTION = "control.rollout.fraction"
DATA_OOCORE_RESIDENT_BYTES = "data.oocore.resident_bytes"
DATA_OOCORE_CURSOR = "data.oocore.cursor"
CLUSTER_HOSTS_LIVE = "cluster.hosts.live"
CLUSTER_HOSTS_DEAD = "cluster.hosts.dead"
WORKLOADS_IFOREST_THRESHOLD = "workloads.iforest.threshold"
WORKLOADS_SAR_CATALOG_ITEMS = "workloads.sar.catalog.items"
TELEMETRY_PROFILE_UNSCOPED_SHARE = "telemetry.profile.unscoped_share"
MOE_LOAD_MAX_OVER_MEAN = "moe.load.max_over_mean"

GAUGES = {
    MOE_LOAD_MAX_OVER_MEAN: "last step's expert load imbalance: the fullest "
                            "held expert's pairs over the mean of the held "
                            "experts', averaged over the expert layers",
    TELEMETRY_PROFILE_UNSCOPED_SHARE: "share of the last parsed capture's "
                                      "device self time that no registered "
                                      "program's scope map puts in a region",
    ANALYSIS_SEMANTIC_CONTRACTS: "hot-path contracts analyzed by the last "
                                 "semantic-tier run",
    ANALYSIS_SEMANTIC_FINDINGS: "findings (incl. contract-import errors) "
                                "from the last semantic-tier run",
    GBDT_HIST_PLAN_BYTES: "resident level-invariant one-hot plane bytes "
                          "built for the current fit "
                          "(MMLSPARK_TPU_HIST=planes)",
    SERVING_QUEUE_DEPTH: "partition queue depth at last enqueue",
    SERVING_BATCH_OCCUPANCY: "live-rows / max_batch of the last "
                             "dispatched batch",
    CHECKPOINT_WRITE_PENDING: "async checkpoint snapshots queued",
    TRAIN_RESUME_STEP: "step the supervisor resumed from",
    CLUSTER_RESUME_EPOCH: "epoch found in this process's prior heartbeat",
    DEVICE_MEM_BYTES_IN_USE: "bytes in use summed over local devices "
                             "(absent where memory_stats() is)",
    DEVICE_MEM_PEAK_BYTES: "peak bytes in use summed over local devices",
    HOST_RSS_BYTES: "host process resident set size (bytes)",
    TRAIN_GOODPUT: "productive fraction of training wall clock "
                   "(1 - (data-wait + checkpoint-stall + lost) / wall)",
    TRAIN_MFU: "model-flops utilization: flops_per_step * steps / "
               "(wall * peak_flops); absent when either flops side is "
               "unknown",
    TRAIN_LOST_SECONDS: "cumulative lost training seconds (restart/replay "
                        "rewinds, injected stalls, failed step attempts)",
    TRAIN_STRAGGLERS: "hosts currently flagged by straggler detection "
                      "(windowed step p50 beyond threshold x fleet median)",
    TELEMETRY_WATCH_TRIPPED: "telemetry watcher rules currently in the "
                             "tripped state",
    QUALITY_DRIFT_MAX: "worst per-column PSI between the frozen "
                       "reference profile and the live serving sketches "
                       "(the quality SLO's drift-ceiling input)",
    ONLINE_BUFFER_PAIRS: "joined pairs currently buffered in the "
                         "LabelFeed (drains on each refit)",
    SERVING_MODEL_VERSION_INFO: "number of model versions currently "
                                "tracked (incumbent + candidate); the "
                                "served version ids ride /versions",
    CANARY_P99_RATIO: "candidate windowed request p99 / incumbent frozen "
                      "p99 (absent until a swap installs a candidate)",
    CANARY_ERROR_BURN: "candidate windowed error rate / the canary error "
                       "budget (absent until a swap installs a candidate)",
    CANARY_DRIFT_DELTA: "candidate live quality.drift.max minus the "
                        "incumbent's frozen drift at swap time",
    CONTROL_ROLLOUT_FRACTION: "traffic fraction the rollout driver "
                              "currently targets for the candidate "
                              "(0 after rollback, 1 at/after promote)",
    DATA_OOCORE_RESIDENT_BYTES: "raw-input bytes the out-of-core stager "
                                "may hold host-resident at once (the "
                                "bounded in-flight window, not the full "
                                "dataset)",
    DATA_OOCORE_CURSOR: "chunks durably binned into the out-of-core "
                        "spill cache so far (the resume cursor a killed "
                        "staging pass restarts from)",
    CLUSTER_HOSTS_LIVE: "hosts currently holding a live lease (beat "
                        "observed within lease_timeout_s of the "
                        "observer's monotonic clock)",
    CLUSTER_HOSTS_DEAD: "hosts declared dead by lease expiry (fenced "
                        "out; stays counted until a fresh observer "
                        "starts)",
    WORKLOADS_IFOREST_THRESHOLD: "contamination score threshold of the "
                                 "last fitted isolation forest (2.0 = "
                                 "labeling disabled)",
    WORKLOADS_SAR_CATALOG_ITEMS: "item-catalog width of the last fitted "
                                 "SAR serving model (the sharded matmul's "
                                 "contraction axis before mesh padding)",
    "control.router.weight.{target}": "weighted-router relative weight "
                                      "per target (host:port), 1..100 — "
                                      "scaled from scraped queue depth "
                                      "and windowed p99",
    "quality.drift.{col}": "per-column PSI drift, reference vs live "
                           "sketch counts over the shared bucket grid "
                           "(refreshed on every exposition scrape)",
    "quality.eval.{metric}": "current streaming-evaluation metric value "
                             "(accuracy/precision/recall or rmse/mae) "
                             "from the delayed-label join",
    "device{ordinal}.mem.bytes_in_use": "per-device bytes in use "
                                        "(memory_stats)",
    "device{ordinal}.mem.peak_bytes": "per-device peak bytes in use "
                                      "(memory_stats)",
    "op.{region}.hbm_util": "per-region achieved / peak HBM bytes/s "
                            "(RooflineLedger; absent when either side "
                            "is unknown)",
    "op.{region}.flops_util": "per-region achieved / peak FLOP/s "
                              "(RooflineLedger; absent when either side "
                              "is unknown)",
}

# ------------------------------------------------------------- histograms
SERVING_REQUEST_QUEUE = "serving.request.queue"
SERVING_REQUEST_TRANSFORM = "serving.request.transform"
SERVING_REQUEST_REPLY = "serving.request.reply"
SERVING_REQUEST_E2E = "serving.request.e2e"
CHECKPOINT_SUBMIT = "checkpoint.submit"
CHECKPOINT_SNAPSHOT = "checkpoint.snapshot"
CHECKPOINT_WRITE = "checkpoint.write"
PLAN_COMPILE = "plan.compile"
TRAIN_STEP_WALL = "train.step.wall"

HISTOGRAMS = {
    PLAN_COMPILE: "plan build / AOT jit compile duration (ms)",
    TRAIN_STEP_WALL: "one training step's wall clock (ms) — the "
                     "straggler detector's windowed p50 source",
    "train.step.{phase}": "per-step phase time (ms): data_wait / host / "
                          "device / checkpoint / lost (StepClock)",
    SERVING_REQUEST_QUEUE: "ingress enqueue -> worker drain, per request "
                           "(ms)",
    SERVING_REQUEST_TRANSFORM: "transform duration per batch (ms)",
    SERVING_REQUEST_REPLY: "reply routing duration per batch (ms)",
    SERVING_REQUEST_E2E: "enqueue -> response routed, per request (ms)",
    CHECKPOINT_SUBMIT: "step-thread time to hand a snapshot to the "
                       "async writer (ms)",
    CHECKPOINT_SNAPSHOT: "snapshot_fn duration on the step thread (ms)",
    CHECKPOINT_WRITE: "checkpoint write duration, sync and async (ms)",
}

# ------------------------------------------------- wall-clock timing labels
DATA_PREFETCH_PUT = "data.prefetch.put"
DATA_BIN_CHUNK = "data.bin_chunk"
DATA_FIT_BINS = "data.fit_bins"
DATA_APPLY_BINS = "data.apply_bins"
DATA_STAGE_BINNED = "data.stage_binned"
DATA_TABLE_TRANSFORM = "data.table_transform"

# ------------------------------------------------------------------ regions
# ONE vocabulary from host span to HLO instruction. A DEVICE region is a
# `jax.named_scope` inside jitted code: the name lands in the compiled
# instructions' `op_name` metadata, and `telemetry.perf.scope_map` joins it
# to a capture's device events by instruction name. A HOST region is a
# `utils.tracing.annotate` span at a layer boundary: a TraceAnnotation on
# the profiler's clock, a ring of durations in the roofline ledger, and a
# wall-clock timing label (TIMINGS below).
LM_EMBED = "lm.embed"
LM_ATTN = "lm.attn"
LM_ATTN_FLASH = "lm.attn.flash"
LM_MLP = "lm.mlp"
LM_HEAD = "lm.head"
LM_OPT = "lm.opt"
LM_CAST = "lm.cast"
LM_GDN = "lm.gdn"
LM_GDN_SCAN = "lm.gdn.scan"
LM_MOE_ROUTER = "lm.moe.router"
LM_MOE_DISPATCH = "lm.moe.dispatch"
LM_MOE_EXPERTS = "lm.moe.experts"
LM_MOE_SHARED = "lm.moe.shared"
LM_CONV = "lm.conv"
LM_CONV_GATE = "lm.conv.gate"
LM_SSM = "lm.ssm"
LM_SSM_SCAN = "lm.ssm.scan"
LM_GMU = "lm.gmu"
LM_LAYERS = "lm.layers"
LM_TICKS = "lm.ticks"
GBDT_HIST = "gbdt.hist"
GBDT_SPLIT = "gbdt.split"
GBDT_ROUTE = "gbdt.route"
GBDT_OBJECTIVE = "gbdt.objective"
GBDT_BIN = "gbdt.bin"

DEVICE_REGIONS = {
    LM_EMBED: "token + position embedding lookup of a microbatch",
    LM_ATTN: "attention sublayer outside its kernels: ln1, q/k/v/o "
             "projections, residual",
    LM_ATTN_FLASH: "(windowed calls: flash_fwd_win, flash_dq_win, "
                   "flash_dkv_win) "
                   "the flash / ring attention call (kernels flash_fwd, "
                   "flash_dq, flash_dkv, flash_stats_fwd and their glue)",
    LM_MLP: "feed-forward sublayer: ln2, gelu MLP, residual",
    LM_HEAD: "final layer norm, tied logits, log-softmax, NLL",
    LM_OPT: "optimizer update + apply_updates over the f32 masters",
    LM_CAST: "per-step f32 -> compute-dtype cast of the parameters",
    LM_GDN: "Gated-DeltaNet mixer outside its recurrence: input norm, "
            "qkv/z/ba projections, gates, out projection, residual, and "
            "on (B, S, H d) slabs (ops/gdn_mixer.py) the causal "
            "convolution + SiLU + L2 norms as one pass (kernels "
            "gdn_prep_fwd, gdn_prep_bwd) and the gated output norm as "
            "another (gdn_post_fwd, gdn_post_bwd); off the TPU or at "
            "other head sizes the same two passes in plain jnp",
    LM_GDN_SCAN: "the chunked gated delta rule (ops/gated_delta.py): on a "
                 "TPU at 128-wide heads the kernels gdn_fwd (forward, and "
                 "again for remat) and gdn_bwd with the running sum of the "
                 "log decay and their layout glue, else its XLA form; "
                 "forward and backward",
    LM_MOE_ROUTER: "expert layer: post norm, router logits, softmax or "
                   "sigmoid scores (+ selection bias), top-k",
    LM_MOE_DISPATCH: "expert layer: the tile plan (sort of the pairs by "
                     "held expert, counts, the tile table) and, round the "
                     "kernels moe_fwd / moe_bwd, the slab copies of x and "
                     "dout and the sort that puts the pairs' "
                     "gradient back; in the XLA loop also its gathers and "
                     "scatter-adds",
    LM_MOE_EXPERTS: "expert layer: the held experts' gated MLPs over the "
                    "tiles of the pairs routed to them; on a TPU the "
                    "kernels moe_fwd / moe_bwd (ops/moe_experts.py), which "
                    "also move the tiles' rows by DMA",
    LM_MOE_SHARED: "expert layer: the shared expert, its sigmoid gate, the "
                   "sum with the routed part, residual",
    LM_CONV: "gated short-convolution mixer outside its gate pass: input "
             "norm, in projection (B, C, u), out projection, residual",
    LM_CONV_GATE: "the short convolution's gate pass on (B, S, d) slabs: "
                  "B * u, the causal depthwise taps, C *, float32 inside; "
                  "forward and backward",
    LM_SSM: "state-space (Mamba) mixer outside its scan: input norm, W_in, "
            "the causal depthwise convolution + SiLU, W_x, W_dt + "
            "softplus, the gate, W_out, residual",
    LM_SSM_SCAN: "the selective scan (ops/selective_scan.py): on a TPU the "
                 "kernels ssm_fwd / ssm_bwd with the broadcast of B and C "
                 "along the lanes before them and the sums after, else "
                 "the XLA form",
    LM_GMU: "Gated Memory Unit: input norm, W_1, memory * silu, W_2, "
            "residual",
    LM_LAYERS: "the family's stage of stacked periods "
               "(PipelinedLMTrainer's call of family.stage) outside every "
               "sublayer's own region, which wins as the innermost: what "
               "the layer / period scans add themselves (stacking and "
               "slicing of the residuals kept for the backward pass, "
               "carries, the lm.shared hand-overs) and any operation of "
               "the family that no sublayer scopes",
    LM_TICKS: "the GPipe tick scan outside the stage, embedding and head: "
              "microbatch slicing, the two conds, the carry and the "
              "ppermute, and in the scan's transpose the sum of the "
              "parameters' cotangents over the ticks",
    GBDT_HIST: "node x feature x bin histogram build (and its psum)",
    GBDT_SPLIT: "best-split search of one level",
    GBDT_ROUTE: "advance rows to their child nodes",
    GBDT_OBJECTIVE: "gradient/hessian, row weights and the margin update "
                    "of one boosting iteration",
    GBDT_BIN: "device bin assignment (apply_bins_device)",
}

# ------------------------------------------------------ remat residuals
# What `jax.checkpoint` keeps of a sublayer it otherwise recomputes: arrays
# tagged with `jax.ad_checkpoint.checkpoint_name` where they are made, kept
# by `save_only_these_names(*REMAT_RESIDUALS)` (models/dnn/hybrid_layers.py
# `checkpoint_sublayers`). A tag lowers to nothing, and a program that
# checkpoints none of them is unchanged by it. A tag keeps an array for the
# equations AFTER it: what a `custom_vjp` hands its backward is tagged
# inside its fwd rule, not at its call.
KEEP_FLASH = "flash.forward"
KEEP_ROUTING = "moe.routing"
KEEP_SHARED = "lm.shared"
KEEP_SSM = "ssm.forward"

REMAT_RESIDUALS = {
    KEEP_FLASH: "the flash forward's output (heads, S, D), in the "
                "activations' dtype, and its row log-sum-exp (heads, S, 1), "
                "float32 (ops/flash_attention.py _flash_fwd_vjp): all the "
                "backward kernels need of a second flash_fwd",
    KEEP_ROUTING: "an expert layer's routing (models/dnn/moe.py): the "
                  "scores over all experts (N, E) float32, which their "
                  "backward reads in place of the logits, the chosen "
                  "experts' ids (N, k) int32 and their scores before they "
                  "are renormalised and scaled (_choose: no second top-k); "
                  "the tile plan's pair ids sorted by held expert (N k,) "
                  "with its starts, tile ends and counts, or the kernels' "
                  "tile-aligned plan (pair ids, weights, tile table) "
                  "(dispatch_plan: no second sort)",
    KEEP_SSM: "the selective scan's output (B, S, channels) in the "
              "activations' dtype and the state each chunk started from "
              "(B, chunks, states, channels) float32 "
              "(ops/selective_scan.py _scan_pallas_fwd): all the backward "
              "kernel needs of a second ssm_fwd; 105 MB a scan call at "
              "8,192 x 5,120, 0.08 GB of the phi4flash cell's peak "
              "(compiled for a described v5e, PR 35: 16.16 GB with, 16.08 "
              "without) for 7 ms a step",
    KEEP_SHARED: "what a layer of a state-space model hands to later "
                 "layers (models/dnn/ssm_layers.py): the memory (the "
                 "memory layer's scan output before its gate) and the KV "
                 "layer's keys and values after projection and bias: the "
                 "forward pass's arrays are what every reader's and the "
                 "maker's own recomputation read",
}

LM_STEP_H2D = "lm.step.h2d"
LM_STEP_DISPATCH = "lm.step.dispatch"
LM_STEP_WAIT = "lm.step.wait"
LM_STEP_GAP = "lm.step.gap"
# the key of PipelinedLMTrainer's ring of step records
# (telemetry.profiler.step_records): a record a step, not a metric
LM_STEP = "lm.step"
GBDT_FIT_FIT_BINS = "gbdt.fit.fit_bins"
GBDT_FIT_BIN_DISPATCH = "gbdt.fit.bin_dispatch"
GBDT_FIT_INIT_SCORE = "gbdt.fit.init_score"
GBDT_FIT_BOOST = "gbdt.fit.boost"
GBDT_FIT_FETCH = "gbdt.fit.fetch"
GBDT_FIT_ASSEMBLE = "gbdt.fit.assemble"
GBDT_ESTIMATOR_PROFILE = "gbdt.estimator.profile"
GBDT_TRANSFORM_SCORE = "gbdt.transform.score"

HOST_REGIONS = {
    LM_STEP_H2D: "PipelinedLMTrainer.step: the batch's device_put",
    LM_STEP_DISPATCH: "PipelinedLMTrainer.step: the step program's "
                      "dispatch (a recompile or a wait for a donated "
                      "buffer shows here)",
    LM_STEP_WAIT: "PipelinedLMTrainer.step: float(loss), the wait for the "
                  "device",
    LM_STEP_GAP: "between two PipelinedLMTrainer.step calls, from the "
                 "exit of lm.step.wait to the next entry of lm.step.h2d: "
                 "family.report, the return, the caller's batch making, "
                 "_check_batch. A noted duration from the second call on "
                 "and no TraceAnnotation (it crosses a call boundary): in "
                 "a capture it is the hole between the two annotations",
    GBDT_FIT_FIT_BINS: "fit_booster: quantile bin fit (what data.fit_bins "
                       "times)",
    GBDT_FIT_BIN_DISPATCH: "fit_booster default path: apply_bins_device + "
                           "put + label upload (host dispatch and H2D; the "
                           "device side is gbdt.bin)",
    GBDT_FIT_INIT_SCORE: "fit_booster: host init score from the labels",
    GBDT_FIT_BOOST: "fit_booster: dispatch of one fused boosting chunk "
                    "(attribute: its iteration count)",
    GBDT_FIT_FETCH: "fit_booster: the one D2H of every chunk's trees, "
                    "where the chunks' device time surfaces",
    GBDT_FIT_ASSEMBLE: "fit_booster: fetched tree arrays to a Booster",
    GBDT_ESTIMATOR_PROFILE: "GBDT estimators: the fit-time quality profile",
    GBDT_TRANSFORM_SCORE: "GBDT models: raw scores of a transform",
}

TIMINGS = {
    **HOST_REGIONS,
    DATA_PREFETCH_PUT: "feeder time spent in device_put",
    DATA_BIN_CHUNK: "per-chunk binning transform wall clock",
    DATA_FIT_BINS: "quantile bin fit wall clock",
    DATA_APPLY_BINS: "parallel bin application wall clock",
    DATA_STAGE_BINNED: "stage_binned end-to-end wall clock",
    DATA_TABLE_TRANSFORM: "ParallelTransform table pass wall clock",
    "data.pool.map[{mode}]": "WorkerPool.map_rows wall clock per backend",
}

# ------------------------------------------------------------------ spans
SERVING_REQUEST_SPAN = "serving.request"
SERVING_PARTITION_TRANSFORM_SPAN = "serving.partition.transform"
SERVING_PLAN_RUN_SPAN = "serving.plan.run"
PLAN_COMPILE_SPAN = "plan.compile"
TRAIN_STEP_SPAN = "train.step"
CHECKPOINT_WRITE_SPAN = "checkpoint.write"
DATA_PREFETCH_SPAN = "data.prefetch"
GBDT_FIT_SPAN = "gbdt.fit"
GBDT_ITERATION_SPAN = "gbdt.iteration"
GBDT_CHUNK_SPAN = "gbdt.chunk"
LM_RUN_STREAM_SPAN = "lm.run_stream"
DEVICE_PROFILE_SPAN = "device.profile"

SPANS = {
    PLAN_COMPILE_SPAN: "one plan build / AOT compile (fingerprint, "
                       "bucket attrs; same name as the histogram, like "
                       "checkpoint.write)",
    SERVING_REQUEST_SPAN: "ingress root span per request (== request id)",
    SERVING_PARTITION_TRANSFORM_SPAN: "worker-hop child span per sampled "
                                      "request",
    SERVING_PLAN_RUN_SPAN: "compiled-plan execution per batch",
    TRAIN_STEP_SPAN: "one supervised training step (covers the fault "
                     "site)",
    CHECKPOINT_WRITE_SPAN: "one checkpoint write attempt (sync/async, "
                           "ok/error)",
    DATA_PREFETCH_SPAN: "DevicePrefetcher lifecycle (depth, items, "
                        "stalls)",
    GBDT_FIT_SPAN: "whole fit_booster call",
    GBDT_ITERATION_SPAN: "one boosting iteration (host loop)",
    GBDT_CHUNK_SPAN: "one fused boosting chunk (scan path)",
    LM_RUN_STREAM_SPAN: "ShardedLMTrainer.run_stream lifecycle",
    DEVICE_PROFILE_SPAN: "utils.tracing.trace device-profile capture",
    "stage.{stage}.{action}": "Timer-wrapped stage fit/transform "
                              "(telemetry=True)",
}

# ----------------------------------------------------------------- events
FAULT_INJECTED_EVENT = "fault.injected"
TRAIN_RESUME_EVENT = "train.resume"
TRAIN_RESTART_EVENT = "train.restart"
TRAIN_PREEMPTED_EVENT = "train.preempted"
TRAIN_STRAGGLER_EVENT = "train.straggler"
TRAIN_CHUNK_REASSIGN_EVENT = "train.chunk.reassign"
TRAIN_HOST_DEAD_EVENT = "train.host.dead"
ELASTIC_PLAN_EVENT = "elastic.plan"
ELASTIC_RESUME_EVENT = "elastic.resume"
TELEMETRY_BUNDLE_EVENT = "telemetry.bundle"
TELEMETRY_PROFILE_EVENT = "telemetry.profile"
TELEMETRY_WATCH_TRIP_EVENT = "telemetry.watch.trip"
SERVING_MODEL_SWAP_EVENT = "serving.model.swap"
CONTROL_ROLLOUT_DEPLOY_EVENT = "control.rollout.deploy"
CONTROL_ROLLOUT_STEP_EVENT = "control.rollout.step"
CONTROL_ROLLOUT_BURN_EVENT = "control.rollout.burn"
CONTROL_ROLLOUT_PROMOTE_EVENT = "control.rollout.promote"
CONTROL_ROLLOUT_ROLLBACK_EVENT = "control.rollout.rollback"
CONTROL_ROLLOUT_RECOVERED_EVENT = "control.rollout.recovered"
ONLINE_TRIP_EVENT = "online.trip"
ONLINE_REFIT_EVENT = "online.refit"
ONLINE_DEPLOY_EVENT = "online.deploy"
ONLINE_PROMOTE_EVENT = "online.promote"
ONLINE_ROLLBACK_EVENT = "online.rollback"

EVENTS = {
    FAULT_INJECTED_EVENT: "one FaultInjector firing (site, index, kind)",
    TRAIN_STRAGGLER_EVENT: "a host's windowed step p50 deviated beyond "
                           "the straggler threshold (host, p50, fleet "
                           "median attrs)",
    TRAIN_HOST_DEAD_EVENT: "a host's lease aged past lease_timeout_s of "
                           "observer-local clock — death verdict "
                           "TRANSITION (host, age_s attrs); the fence "
                           "bump rides the same transition",
    ELASTIC_PLAN_EVENT: "survivor-side shrink plan derived after a death "
                        "verdict (dead, survivors, restaged-chunk "
                        "attrs) — ordered after train.host.dead",
    ELASTIC_RESUME_EVENT: "training resumed from the committed fleet "
                          "manifest on the shrunk host set (step, "
                          "survivors attrs) — ordered after elastic.plan",
    TRAIN_CHUNK_REASSIGN_EVENT: "ChunkPlanner drained a flagged host's "
                                "pending chunks to healthy hosts "
                                "(from_host, to_hosts, chunks attrs) — "
                                "ordered after the train.straggler flag "
                                "that triggered it",
    TELEMETRY_BUNDLE_EVENT: "one flight-recorder bundle written (reason, "
                            "path)",
    TELEMETRY_PROFILE_EVENT: "one device-profile capture written "
                             "(reason, path, parsed op count)",
    TELEMETRY_WATCH_TRIP_EVENT: "a watched telemetry series breached its "
                                "rule (key, kind, value, bound/baseline "
                                "attrs)",
    TRAIN_RESUME_EVENT: "supervisor resumed from a checkpoint",
    TRAIN_RESTART_EVENT: "supervisor restarted the step loop from the "
                         "in-memory snapshot",
    TRAIN_PREEMPTED_EVENT: "supervisor took the preemption exit",
    SERVING_MODEL_SWAP_EVENT: "one committed install_model hot-swap "
                              "(old/new version ids, plan-cache size "
                              "attrs)",
    CONTROL_ROLLOUT_DEPLOY_EVENT: "rollout started: candidate installed "
                                  "on the first traffic step (candidate/"
                                  "incumbent version, fraction attrs)",
    CONTROL_ROLLOUT_STEP_EVENT: "rollout advanced one traffic step "
                                "(fraction, workers attrs)",
    CONTROL_ROLLOUT_BURN_EVENT: "rollout observed a burn or watch trip — "
                                "the rollback trigger (reason attr)",
    CONTROL_ROLLOUT_PROMOTE_EVENT: "rollout auto-promoted the candidate "
                                   "after its soak window",
    CONTROL_ROLLOUT_ROLLBACK_EVENT: "rollout re-installed the incumbent "
                                    "fleet-wide (reason, workers attrs)",
    CONTROL_ROLLOUT_RECOVERED_EVENT: "post-rollback fleet SLO verdict "
                                     "returned to ok (ok attr False when "
                                     "the wait timed out)",
    ONLINE_TRIP_EVENT: "continuous learner triggered a refit cycle "
                       "(reason drift/floor-burn, buffered-pairs attrs) "
                       "— always journaled before online.refit",
    ONLINE_REFIT_EVENT: "incremental refit completed: candidate "
                        "ModelVersion + lineage (version, updates, "
                        "examples, loss attrs)",
    ONLINE_DEPLOY_EVENT: "candidate handed to the rollout gate "
                         "(version attr) — journaled after online.refit, "
                         "before the rollout's own deploy event",
    ONLINE_PROMOTE_EVENT: "rollout gate promoted the online candidate "
                          "(version attr); terminal event of a healthy "
                          "cycle",
    ONLINE_ROLLBACK_EVENT: "rollout gate rejected the online candidate — "
                           "incumbent restored, learner rewound to the "
                           "pre-refit snapshot (version attr)",
    "registry.{action}": "registry HTTP hops (register/unregister) under "
                         "the caller's propagated trace",
}

# ------------------------------------------------------------- fault sites
# Fire sites keep their literals inline (see module docstring); this is
# the canonical list the analyzer validates both code and chaos tests
# against. Patterned sites carry the per-call index in the name.
FAULT_SITES = {
    "serving.ingress": "selector-transport ingress, fired per parsed "
                       "request (kind `reset` drops the socket)",
    "serving.worker": "partition worker between batch read and commit",
    "train.step{step}": "supervisor step k, fired before the step fn",
    "train.ckpt.write": "checkpoint write path (sync and async)",
    "train.ckpt.read": "checkpoint restore path",
    "cluster.heartbeat": "Heartbeat.beat() before the atomic write",
    "cluster.lease.expire": "HostLeases.check(), fired once per "
                            "(round, host) in sorted host order (kind "
                            "`expire` forces a false-positive death "
                            "verdict on that host — fencing then "
                            "rejects its next beat exactly once; kind "
                            "`error` skips the whole check round)",
    "elastic.commit": "FleetCheckpoint.commit between the manifest "
                      "tmp-write and its os.replace (kind `crash` "
                      "models the leader dying mid-commit — no "
                      "manifest lands, the next leader re-commits; a "
                      "torn manifest is never restored)",
    "data.worker.chunk{index}": "ingest pool, fired before chunk i's "
                                "transform",
    "data.oocore.stage{index}": "out-of-core stager, fired before chunk "
                                "i's binned rows are written to the "
                                "spill cache (kind `error` aborts "
                                "staging mid-dataset — the durable "
                                "cursor resumes from the last flushed "
                                "chunk; `delay` stretches staging so a "
                                "SIGTERM can land mid-epoch)",
    "data.planner.reassign": "ChunkPlanner.reassign, fired before the "
                             "pending-chunk migration commits (kind "
                             "`error` skips this reassignment round — "
                             "the flagged host keeps its chunks until "
                             "the next straggler check; `delay` "
                             "stretches the actuation)",
    "fuzz.http": "corrupt_bytes stream for the malformed-HTTP fuzz "
                 "corpus",
    "checkpoint": "corrupt_file default site (checkpoint corruption "
                  "tests)",
    "quality.label": "StreamingEvaluator.record_label, fired per "
                     "arriving label (kind `drop` loses the label "
                     "before the join — counted quality.labels.dropped)",
    "serving.swap": "ServingTransform.install_model, fired after the new "
                    "handle is built but before it commits (a raise "
                    "rolls back to the incumbent — counted "
                    "serving.model.swap_errors)",
    "control.rollout.poll": "RolloutDriver fleet scrape, fired before "
                            "each poll round (kind `error` counts "
                            "control.rollout.poll_errors and skips the "
                            "round; `delay` stretches the poll)",
    "online.refit": "ContinuousLearner refit, fired after the minibatch "
                    "updates but before the candidate model is built (a "
                    "raise rewinds the learner to the pre-refit snapshot "
                    "and retries — counted online.refit_retries; the "
                    "incumbent keeps serving throughout)",
    "workloads.sar.refit": "SARServing._fit, fired after the similarity "
                           "build but before the model assembles (a "
                           "raise aborts the candidate fit — a serving "
                           "incumbent is untouched because install_model "
                           "only ever sees a whole fitted model)",
}

# ------------------------------------------------- patterned-name helpers
def data_pool_maps(mode: str) -> str:
    """data.pool.{mode}_maps — per-backend WorkerPool map counter."""
    return f"data.pool.{mode}_maps"


def data_pool_map_timing(mode: str) -> str:
    """data.pool.map[{mode}] — per-backend map wall-clock label."""
    return f"data.pool.map[{mode}]"


def breaker_trips(breaker: str) -> str:
    """{breaker}.trips — per-breaker trip counter."""
    return f"{breaker}.trips"


def stage_span(stage: str, action: str) -> str:
    """stage.{stage}.{action} — Timer span label."""
    return f"stage.{stage}.{action}"


def device_mem_in_use(ordinal: int) -> str:
    """device{ordinal}.mem.bytes_in_use — per-device in-use gauge."""
    return f"device{ordinal}.mem.bytes_in_use"


def device_mem_peak(ordinal: int) -> str:
    """device{ordinal}.mem.peak_bytes — per-device peak gauge."""
    return f"device{ordinal}.mem.peak_bytes"


def train_step_phase(phase: str) -> str:
    """train.step.{phase} — per-phase step-time histogram."""
    return f"train.step.{phase}"


def gbdt_hist_route(route: str) -> str:
    """gbdt.hist.route.{route} — per-route kernel-selection counter."""
    return f"gbdt.hist.route.{route}"


def op_hbm_util(region: str) -> str:
    """op.{region}.hbm_util — per-region roofline HBM utilization."""
    return f"op.{region}.hbm_util"


def op_flops_util(region: str) -> str:
    """op.{region}.flops_util — per-region roofline FLOPs utilization."""
    return f"op.{region}.flops_util"


def quality_drift(col: str) -> str:
    """quality.drift.{col} — per-column PSI drift gauge."""
    return f"quality.drift.{col}"


def quality_eval(metric: str) -> str:
    """quality.eval.{metric} — streaming-evaluation metric gauge."""
    return f"quality.eval.{metric}"


def control_router_weight(target: str) -> str:
    """control.router.weight.{target} — per-target router weight gauge."""
    return f"control.router.weight.{target}"
