"""Environment-variable configuration (reference `docs/faq/env_var.md`).

Every documented MXNET_* knob is registered here with its mapping onto
this framework.  Three honest statuses:

* honored    — changes behavior (the entry names the consumer)
* subsumed   — the mechanism it tuned does not exist on the XLA/TPU
  design (e.g. GPU memory pools, NNPACK, OpenMP tuning); reading it is
  harmless and a debug log records that it was ignored
* accepted   — parsed and exposed via `config.get`, consumers may adopt

`config.get(name, default)` is the single read path: values are parsed
to the registered type, and unknown MXNET_* variables in the process
environment produce one warning each (catching typos, the failure mode
env-knob systems actually have).
"""
from __future__ import annotations

import logging
import os

_LOG = logging.getLogger(__name__)

_BOOL = lambda s: s not in ("0", "false", "False", "")

# name -> (type, default, status, note)
KNOBS = {
    # -- engine / execution --------------------------------------------------
    "MXNET_ENGINE_TYPE": (str, "ThreadedEnginePerDevice", "honored",
                          "engine.py: NaiveEngine forces synchronous "
                          "dispatch (block_until_ready per op)"),
    "MXNET_EXEC_BULK_EXEC_INFERENCE": (_BOOL, True, "honored",
                                       "engine.bulk scopes batch host "
                                       "staging at inference"),
    "MXNET_EXEC_BULK_EXEC_TRAIN": (_BOOL, True, "honored",
                                   "engine.bulk scopes batch host staging "
                                   "in training"),
    "MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN": (int, 15, "subsumed",
                                            "XLA fuses whole graphs; no "
                                            "segment cap applies"),
    "MXNET_EXEC_ENABLE_INPLACE": (_BOOL, True, "subsumed",
                                  "XLA buffer assignment handles aliasing"),
    "MXNET_EXEC_NUM_TEMP": (int, 1, "subsumed", "no temp-space workspace"),
    # -- threading -----------------------------------------------------------
    "MXNET_CPU_WORKER_NTHREADS": (int, 4, "honored",
                                  "default preprocess_threads for "
                                  "ImageRecordIter / DataLoader workers"),
    "MXNET_CPU_PRIORITY_NTHREADS": (int, 4, "subsumed", "no priority queue"),
    "MXNET_CPU_NNPACK_NTHREADS": (int, 4, "subsumed", "no NNPACK"),
    "MXNET_MP_WORKER_NTHREADS": (int, 1, "accepted", "dataloader workers"),
    "MXNET_OMP_MAX_THREADS": (int, 0, "honored",
                              "exported as OMP_NUM_THREADS for the native "
                              "IO library's OpenMP loops"),
    # -- gpu/memory knobs (no CUDA on this design) ---------------------------
    "MXNET_GPU_WORKER_NTHREADS": (int, 2, "subsumed", "no CUDA streams"),
    "MXNET_GPU_COPY_NTHREADS": (int, 2, "subsumed", "no CUDA copy engine"),
    "MXNET_GPU_MEM_POOL_RESERVE": (int, 5, "subsumed",
                                   "HBM is managed by PJRT; see "
                                   "storage.memory_stats()"),
    "MXNET_GPU_MEM_POOL_TYPE": (str, "Naive", "subsumed", "PJRT allocator"),
    "MXNET_GPU_MEM_POOL_ROUND_LINEAR_CUTOFF": (int, 24, "subsumed", ""),
    "MXNET_GPU_MEM_POOL_PAGE_SIZE": (int, 4096, "subsumed", ""),
    "MXNET_ENABLE_GPU_P2P": (_BOOL, True, "subsumed",
                             "ICI collectives are XLA-scheduled"),
    # -- kvstore / distributed ----------------------------------------------
    "MXNET_KVSTORE_REDUCTION_NTHREADS": (int, 4, "subsumed",
                                         "reduce is one XLA collective"),
    "MXNET_DECODE_SLOTS": (int, 8, "honored",
                           "KV-cache rows the continuous-batching "
                           "DecodeEngine advances per tick (the decode-"
                           "step program's fixed batch dimension)"),
    "MXNET_DECODE_BUCKETS": (str, "8,16,32", "honored",
                             "prompt-length bucket ladder for decode "
                             "prefill: one compiled signature per "
                             "bucket, prompts padded up"),
    "MXNET_DECODE_ADMIT_PER_TICK": (int, 2, "honored",
                                    "max sequences admitted (prefilled) "
                                    "per decode tick, so long prefill "
                                    "bursts never stall the running "
                                    "slots' decode step"),
    "MXNET_DECODE_MAX_NEW": (int, 32, "honored",
                             "default generation budget per sequence "
                             "when a request does not set "
                             "max_new_tokens"),
    "MXNET_KVSTORE_BIGARRAY_BOUND": (int, 1000000, "honored",
                                     "dist server round accounting "
                                     "threshold (dist/server.py)"),
    "MXNET_KVSTORE_USETREE": (_BOOL, False, "subsumed",
                              "topology is XLA's concern on the torus"),
    "MXNET_ENABLE_GPU_P2P_COMM": (_BOOL, True, "subsumed", ""),
    # -- io ------------------------------------------------------------------
    "MXNET_USE_NATIVE_IO": (_BOOL, True, "honored",
                            "native.py: disables the C++ IO library"),
    "MXNET_CUDNN_AUTOTUNE_DEFAULT": (int, 1, "subsumed", "no cuDNN"),
    # -- model zoo / home ----------------------------------------------------
    "MXNET_HOME": (str, os.path.join(os.path.expanduser("~"), ".mxnet"),
                   "honored", "gluon model_zoo root directory"),
    # -- profiling / debug ---------------------------------------------------
    "MXNET_PROFILER_AUTOSTART": (_BOOL, False, "honored",
                                 "profiler.py starts a jax trace at import"),
    "MXNET_PROFILER_MODE": (int, 0, "accepted", ""),
    "MXNET_EXEC_VERBOSE_LOGGING": (_BOOL, False, "accepted", ""),
    "MXNET_SUBGRAPH_BACKEND": (str, "", "honored",
                               "symbol.simple_bind partitions with the "
                               "named subgraph property"),
    "MXNET_SUBGRAPH_VERBOSE": (_BOOL, True, "accepted", ""),
    "MXNET_SAFE_ACCUMULATION": (_BOOL, False, "honored",
                                "fp32 accumulation for low-precision "
                                "reductions (BatchNorm stats, optimizers "
                                "with multi_precision)"),
    # -- numerics ------------------------------------------------------------
    "MXNET_FORCE_F32_MATMUL": (_BOOL, False, "honored",
                               "sets jax default_matmul_precision=highest "
                               "(full-fp32 MXU inputs; this framework's "
                               "own knob)"),
    # -- TPU-framework-specific knobs ---------------------------------------
    "MXNET_FUSED_TRAIN_STEP": (_BOOL, True, "honored",
                               "Module.fit/Estimator.fit single-program "
                               "fused train step (fused.py)"),
    "MXNET_FUSED_STEP_BLOCK": (int, 8, "honored",
                               "K train steps per dispatch in Module.fit/"
                               "Estimator.fit: ONE lax.scan program runs K "
                               "stacked batches, amortizing host dispatch "
                               "(batch_end callbacks then fire in bursts "
                               "of K; set 1 to restore per-step dispatch)"),
    "MXNET_FUSED_BACKWARD": (_BOOL, True, "honored",
                             "eager loss.backward() as ONE jitted tape "
                             "replay per structure (autograd.py)"),
    "MXNET_FUSED_SCAN": (_BOOL, True, "honored",
                         "scan-over-layers graph dedup: runs of "
                         "structurally identical layer blocks lower to "
                         "ONE lax.scan body over per-layer params "
                         "stacked in-program (Symbol graphs via "
                         "analysis.scan_plan, Gluon HybridSequential "
                         "via identical-config children), shrinking "
                         "the graph XLA compiles while params/"
                         "checkpoints keep per-layer layout; "
                         "bit-identical to the inlined path"),
    "MXNET_FUSED_AUTODONATE": (_BOOL, True, "honored",
                               "donate per-step staged inputs whose "
                               "buffers provably die inside the fused "
                               "step (trace-time jaxpr liveness via "
                               "analysis.cost), letting XLA reuse them "
                               "for intermediates — peak-HBM relief; "
                               "staged inputs are re-owned first "
                               "(reown_for_donation discipline)"),
    "MXNET_KVSTORE_BIGARRAY_BOUND": (int, 1000000, "honored",
                                     "arrays with more elements flat-split "
                                     "into one range per server "
                                     "(dist kvstore key-range sharding)"),
    "MXNET_KVSTORE_COLLECTIVE": (_BOOL, True, "honored",
                                 "dist_sync gradients ride XLA collectives "
                                 "instead of the socket server"),
    "MXNET_KVSTORE_BUCKET_MB": (float, 32, "honored",
                                "gradient all-reduce bucket size cap on "
                                "kvstore='tpu'/'device': a batched push "
                                "packs keys into size-capped buckets "
                                "(priority order: last-produced grads "
                                "first) and dispatches each bucket's "
                                "collective asynchronously — O(buckets) "
                                "dispatches per step, overlapped with "
                                "host-side assembly"),
    "MXNET_KVSTORE_OVERLAP": (_BOOL, True, "honored",
                              "async per-bucket dispatch on the "
                              "collective kvstore (bucket k's all-reduce "
                              "executes while bucket k+1 assembles); 0 "
                              "blocks after each bucket"),
    "MXNET_MESH": (str, "", "honored",
                   "composed device-mesh spec for the fused train step, "
                   "e.g. 'dp=8' or 'dp=4,tp=2' (axis sizes multiply to "
                   "the device count; the dp axis shards the batch, "
                   "other axes are available to TP/PP-sharded params) — "
                   "the Module.fit/init_optimizer mesh= argument wins "
                   "over the env"),
    "MXNET_POD_SPMD": (_BOOL, True, "honored",
                       "pod SPMD fast path in the fused train step: the "
                       "whole step runs inside shard_map over the dp "
                       "axis and gradients exchange in O(buckets) "
                       "flatten-concat psum collectives "
                       "(MXNET_KVSTORE_BUCKET_MB caps a bucket) instead "
                       "of GSPMD's one all-reduce per tensor — fewer "
                       "cross-device barriers per step; falls back to "
                       "the global-view lowering for RNG/batch-"
                       "normalized/reduced-output graphs or composed "
                       "(tp/pp) meshes"),
    "MXNET_ZERO": (_BOOL, False, "honored",
                   "ZeRO-style weight-update sharding in the fused step: "
                   "optimizer-state tensors shard over the dp axis, so "
                   "XLA lowers the gradient exchange to reduce-scatter, "
                   "updates only the local shard, and all-gathers the "
                   "new weights (per-device optimizer memory 1/N)"),
    # -- mxcost static cost analysis (analysis/cost.py) ----------------------
    "MXNET_COST_PROFILE": (str, "tpu-v3", "honored",
                           "device profile the mxcost roofline "
                           "classifies against (analysis/cost.py "
                           "PROFILES: tpu-v3, tpu-v4, cpu-host)"),
    "MXNET_COST_DONATE_MIN_MB": (float, 1.0, "honored",
                                 "minimum buffer size for a donation-"
                                 "opportunity finding (step-boundary "
                                 "buffers that die undonated)"),
    "MXNET_SHARD_MIN_MB": (float, 1.0, "honored",
                           "mxshard (analysis/sharding.py) finding "
                           "floor: implicit-replication and "
                           "hidden-reshard fire only for tensors at "
                           "least this many MB"),
    # -- resilience (this framework's own knobs) -----------------------------
    "MXNET_FAULTS": (str, "", "honored",
                     "resilience/faults.py: deterministic fault-injection "
                     "spec, e.g. 'seed=7;transport.send:drop(at=3)'"),
    "MXNET_FAULTS_LOG": (str, "", "honored",
                         "append one JSON line per fired fault/retry event "
                         "(chaos-run artifacts; tools/run_chaos.py)"),
    "MXNET_PS_REQUEST_TIMEOUT": (float, 330.0, "honored",
                                 "dist transport per-request timeout; must "
                                 "exceed the server's 300s sync waits"),
    "MXNET_PS_CONNECT_WAIT": (float, 90.0, "honored",
                              "dist transport initial-connect window "
                              "(covers the worker/server startup race)"),
    "MXNET_PS_RECONNECT_WAIT": (float, 5.0, "honored",
                                "dist transport mid-request reconnect "
                                "window (failover diagnosis speed)"),
    "MXNET_PS_MAX_RETRIES": (int, 3, "honored",
                             "dist transport request attempts (backoff + "
                             "jitter; resends are idempotent via seq)"),
    "MXNET_PS_BREAKER_THRESHOLD": (int, 2, "honored",
                                   "consecutive exhausted-retry failures "
                                   "before a parameter server is declared "
                                   "lost (ServerLostError)"),
    "MXNET_PS_BREAKER_RESET_S": (float, 30.0, "honored",
                                 "open->half-open window of the per-server "
                                 "circuit breaker"),
    "MXNET_SERVING_BREAKER_THRESHOLD": (int, 5, "honored",
                                        "consecutive failed batches before "
                                        "a served model's breaker opens "
                                        "(fail fast, shed load)"),
    "MXNET_SERVING_BREAKER_RESET_S": (float, 30.0, "honored",
                                      "serving breaker open->half-open "
                                      "probe window"),
    # -- multi-replica serving router (serving/router.py) --------------------
    "MXNET_ROUTER_HEALTH_INTERVAL_S": (float, 0.5, "honored",
                                       "router health thread probe "
                                       "interval per replica (heartbeat; "
                                       "every k-th is a deepcheck)"),
    "MXNET_ROUTER_HEALTH_DEADLINE_S": (float, 5.0, "honored",
                                       "probe silence before a replica "
                                       "is declared dead and its "
                                       "in-flight requests fail over "
                                       "(a probe-failure BURST inside "
                                       "the deadline only suspends "
                                       "dispatch — no false eviction)"),
    "MXNET_ROUTER_DEEPCHECK_EVERY": (int, 8, "honored",
                                     "every Nth health probe runs a real "
                                     "bucket-1 inference through the "
                                     "compiled ladder instead of a cheap "
                                     "heartbeat (0 disables deepchecks)"),
    "MXNET_ROUTER_MAX_DISPATCHES": (int, 3, "honored",
                                    "dispatch attempts per request "
                                    "across replica deaths before the "
                                    "request fails (failover budget)"),
    "MXNET_ROUTER_SHED_BEST_EFFORT_MS": (float, 25.0, "honored",
                                         "estimated fleet wait beyond "
                                         "which best_effort requests "
                                         "are shed (the FIRST class to "
                                         "degrade under overload)"),
    "MXNET_ROUTER_SHED_BATCH_MS": (float, 100.0, "honored",
                                   "estimated fleet wait beyond which "
                                   "batch-class requests are shed"),
    "MXNET_ROUTER_SHED_INTERACTIVE_MS": (float, 1000.0, "honored",
                                         "estimated fleet wait beyond "
                                         "which even interactive "
                                         "requests are shed (the last "
                                         "line before queue collapse)"),
    # -- cross-host serving fleet (serving/fleet.py) -------------------------
    "MXNET_FLEET_TICK_S": (float, 0.5, "honored",
                           "FleetManager control-loop tick: the autoscaler "
                           "samples the router's est-wait signal and "
                           "reconciles the fleet to target once per tick"),
    "MXNET_FLEET_SLO_MS": (float, 100.0, "honored",
                           "the autoscaler's SLO on the admission "
                           "est-wait signal: sustained waits above it "
                           "scale the fleet up (the same queue-model "
                           "number the router sheds on)"),
    "MXNET_FLEET_UP_AFTER_S": (float, 3.0, "honored",
                               "est-wait must breach the SLO for this "
                               "long, uninterrupted, before a scale-up "
                               "(a transient burst never spawns)"),
    "MXNET_FLEET_DOWN_AFTER_S": (float, 30.0, "honored",
                                 "the fleet must be idle (est-wait under "
                                 "the idle threshold, nothing in flight) "
                                 "this long before a scale-down retires "
                                 "a replica through the drain path"),
    "MXNET_FLEET_IDLE_FRACTION": (float, 0.1, "honored",
                                  "idle threshold as a fraction of the "
                                  "SLO; est-wait between idle and SLO is "
                                  "the hysteresis dead band (both streaks "
                                  "reset, so a flapping signal can never "
                                  "thrash the fleet)"),
    "MXNET_FLEET_COOLDOWN_S": (float, 10.0, "honored",
                               "minimum spacing between scale events: "
                               "every action arms it, rate-limiting even "
                               "a pathological signal to one event per "
                               "window"),
    "MXNET_FLEET_MIN_REPLICAS": (int, 1, "honored",
                                 "scale-down floor (and the default "
                                 "initial target)"),
    "MXNET_FLEET_MAX_REPLICAS": (int, 8, "honored",
                                 "scale-up ceiling: breaches past it are "
                                 "counted (stats.signal.clamped_at_max), "
                                 "not acted on"),
    "MXNET_FLEET_HOST_HEARTBEAT_S": (float, 1.0, "honored",
                                     "interval of the fleet's host-agent "
                                     "heartbeats (fed into the "
                                     "dist.membership table)"),
    "MXNET_FLEET_HOST_DEADLINE_S": (float, 5.0, "honored",
                                    "heartbeat silence before a HOST is "
                                    "declared dead: all its replicas are "
                                    "marked dead at once, in-flight "
                                    "requests fail over, and the fleet "
                                    "backfills on surviving hosts"),
    # -- continuous train-to-serve loop (loop/) ------------------------------
    "MXNET_LOOP_PUBLISH_STEPS": (int, 100, "honored",
                                 "trained steps between registry "
                                 "publishes of the newest guardian-"
                                 "healthy checkpoint (0 disables the "
                                 "step cadence)"),
    "MXNET_LOOP_PUBLISH_SECS": (float, 0.0, "honored",
                                "wall-clock publish cadence in seconds "
                                "(0 disables; combines with the step "
                                "cadence — whichever fires first)"),
    "MXNET_LOOP_CANARY_TOL": (float, 0.02, "honored",
                              "canary gate tolerance: a candidate may "
                              "score up to this much BELOW the "
                              "incumbent on the pinned holdout and "
                              "still promote; anything worse is "
                              "rejected and stamped, never retried"),
    "MXNET_LOOP_POLL_S": (float, 2.0, "honored",
                          "LoopController registry poll interval"),
    "MXNET_LOOP_FRESHNESS_SLO_S": (float, 600.0, "honored",
                                   "freshness SLO: max acceptable "
                                   "loop.freshness_lag_s (data-seen "
                                   "watermark -> version live on the "
                                   "fleet), gated in LOOP_REPORT.json"),
    # -- training guardian (resilience/guardian.py) --------------------------
    "MXNET_GUARDIAN": (_BOOL, True, "honored",
                       "training health guardian in Module.fit: in-graph "
                       "all-finite + gradient-norm health word on the "
                       "fused step, skip-batch on non-finite updates, "
                       "rollback-to-last-good on loss spikes (with a "
                       "checkpoint_dir), bad-batch quarantine"),
    "MXNET_GUARDIAN_INTERVAL": (int, 8, "honored",
                                "trained steps between health-word "
                                "polls: the device scalars accumulate "
                                "and are gathered in ONE host read per "
                                "interval (no per-step host sync). "
                                "An unforced poll never waits for the "
                                "newest dispatch, so a step is "
                                "diagnosed at most interval + one "
                                "dispatch's steps (MXNET_FUSED_STEP_"
                                "BLOCK) after it ran; forced polls "
                                "(epoch end, checkpoint and preemption "
                                "snapshots) are exact"),
    "MXNET_GUARDIAN_SPIKE_WINDOW": (int, 16, "honored",
                                    "EWMA window (and warmup step "
                                    "count) of the loss-spike detector "
                                    "over the gradient-norm signal"),
    "MXNET_GUARDIAN_SPIKE_K": (float, 6.0, "honored",
                               "k-sigma divergence of the health "
                               "signal over its EWMA diagnosed as a "
                               "loss spike (rollback trigger)"),
    "MXNET_GUARDIAN_MAX_FAILURES": (int, 3, "honored",
                                    "consecutive unhealthy steps "
                                    "before the guardian escalates to "
                                    "TrainingDivergedError naming "
                                    "step, signal, and data shard"),
    "MXNET_GUARDIAN_MAX_ROLLBACKS": (int, 2, "honored",
                                     "rollback-to-last-good budget per "
                                     "fit; past it a spike escalates "
                                     "to TrainingDivergedError"),
    "MXNET_GUARDIAN_QUARANTINE": (str, "", "honored",
                                  "bad-data quarantine JSONL path "
                                  "(default: <checkpoint_dir>/"
                                  "quarantine.jsonl); quarantined "
                                  "positions/records are skipped on "
                                  "resume"),
    "MXNET_FIT_MAX_RESTARTS": (int, 2, "honored",
                               "Module.fit auto-restarts from the last "
                               "checkpoint after ServerLostError or "
                               "CollectiveTimeoutError at most this many "
                               "times"),
    # -- elastic multi-host supervisor (resilience/supervisor.py) -----------
    "MXNET_SUPERVISOR": (_BOOL, True, "honored",
                         "JobSupervisor around multi-worker Module.fit: "
                         "heartbeat/membership, hung-collective watchdog, "
                         "straggler detection, shrink-and-resume"),
    "MXNET_SUPERVISOR_HEARTBEAT_S": (float, 2.0, "honored",
                                     "heartbeat interval to the pod "
                                     "coordinator (the root parameter "
                                     "server)"),
    "MXNET_SUPERVISOR_DEADLINE_S": (float, 10.0, "honored",
                                    "heartbeat silence before a host is "
                                    "declared dead in the membership "
                                    "view"),
    "MXNET_SUPERVISOR_COLLECTIVE_TIMEOUT_S": (float, 120.0, "honored",
                                              "watchdog deadline turning "
                                              "a hung cross-host "
                                              "collective into a "
                                              "CollectiveTimeoutError "
                                              "naming the absent hosts"),
    "MXNET_SUPERVISOR_STRAGGLER_K": (float, 3.0, "honored",
                                     "k-sigma divergence of a host's "
                                     "step-time EWMA from the pod median "
                                     "flagged as a straggler finding"),
    "MXNET_SUPERVISOR_SHRINK_BARRIER_S": (float, 30.0, "honored",
                                          "deadline of the epoch-fenced "
                                          "shrink barrier (survivors "
                                          "agreeing on the new world "
                                          "size)"),
    "MXNET_SUPERVISOR_EPOCH": (int, 0, "honored",
                               "membership epoch a (re)starting worker "
                               "registers at — set by the shrink-and-"
                               "resume path, not by hand; a stale epoch "
                               "is fenced out by the coordinator"),
    "MXNET_INTERNAL_CONV_LAYOUT": (str, "NCHW", "honored",
                                   "NHWC internal conv/pool/BN execution "
                                   "(ops/layout.py; measured ~parity on "
                                   "v5e, default off)"),
    "MXNET_FLASH_INTERPRET": (_BOOL, False, "honored",
                              "run the Pallas kernels (flash attention, "
                              "fused FC+ReLU) in interpreter mode — the "
                              "only way interpret mode is ever selected "
                              "(CPU testing)"),
    "MXNET_FLASH_VMEM_MB": (float, 10.0, "honored",
                            "VMEM budget steering the whole-KV kernel vs "
                            "the KV-streaming grid (long-context) variant"),
    # -- unified program cache (compile/) ------------------------------------
    "MXNET_PROGRAM_CACHE": (_BOOL, True, "honored",
                            "unified program cache (compile/): fused "
                            "train/inference/CachedOp programs share one "
                            "per-signature cache with AOT build + stats; "
                            "0 restores plain per-site jax.jit"),
    "MXNET_PROGRAM_CACHE_DIR": (str, "", "honored",
                                "persistent disk tier: XLA serialized "
                                "executables keyed by graph-hash x shapes "
                                "x dtypes x donation x device fingerprint "
                                "(CRC'd, atomic-rename entries); a second "
                                "process loads instead of recompiling"),
    "MXNET_PROGRAM_CACHE_LIMIT_MB": (int, 2048, "honored",
                                     "disk-tier size cap; stalest entries "
                                     "evicted (LRU by mtime) past it"),
    "MXNET_PROGRAM_CACHE_CHECKPOINT": (_BOOL, True, "honored",
                                       "ship a programs/ payload with "
                                       "elastic checkpoints so resumed "
                                       "jobs skip XLA compilation "
                                       "(checkpoint dir gains serialized "
                                       "executables; resume adds them as "
                                       "a cache source)"),
    "MXNET_ANALYSIS": (_BOOL, False, "honored",
                       "analysis/: runtime trace passes — per-parameter "
                       "donation tracking, host-sync attribution inside "
                       "Module.fit/Trainer.step, recompilation audit "
                       "(read with analysis.runtime_report())"),
    # -- concurrency sanitizer (analysis/tsan.py) ----------------------------
    "MXNET_TSAN": (_BOOL, False, "honored",
                   "analysis/tsan.py: runtime concurrency sanitizer — "
                   "locks built via analysis.locks feed a process-wide "
                   "lock-order graph (deadlock cycles reported before "
                   "they hang), registered shared state gets lockset "
                   "race attribution, blocking calls under contended "
                   "locks and leaked/unjoined threads are flagged; "
                   "unset, the lock shims ARE the plain threading "
                   "objects (zero overhead)"),
    "MXNET_TSAN_LOG": (str, "", "honored",
                       "write the sanitizer's findings + lock-order "
                       "graph as one JSON artifact at process exit "
                       "(rendered by tools/mxlint.py --tsan-report)"),
    "MXNET_TSAN_RAISE": (_BOOL, False, "honored",
                         "escalate a NEW lock-order deadlock cycle to "
                         "an MXNetError at the acquisition site instead "
                         "of only recording a finding (the lock is "
                         "released before raising)"),
    # -- production data plane (io_plane.py) ---------------------------------
    "MXNET_IO_RING": (_BOOL, True, "honored",
                      "h2d staging ring: Module.fit (and the gluon "
                      "Estimator) wrap the training iterator in a "
                      "DevicePrefetchIter — batches stage into reusable "
                      "host buffers, transfer on a dedicated mx-io-h2d "
                      "thread, and park in a device-resident prefetch "
                      "queue, so the train loop never blocks on "
                      "device_put; 0 restores the blocking path"),
    "MXNET_IO_PREFETCH": (int, 3, "honored",
                          "device-resident prefetch depth of the h2d "
                          "ring (bounded queue of already-transferred "
                          "batches; floor 2 — double buffering is the "
                          "minimum that overlaps transfer with compute)"),
    "MXNET_IO_STAGING": (_BOOL, True, "honored",
                         "assemble batches into reusable preallocated "
                         "host staging buffers before transfer (the "
                         "pinned-memory pattern: stable buffers, one "
                         "copy that also applies the dtype cast); 0 "
                         "transfers straight from the producer's arrays"),
    "MXNET_IO_UINT8_WIRE": (_BOOL, True, "honored",
                            "ImageRecordIter(device_augment='auto') "
                            "resolves to uint8-on-the-wire: the host "
                            "stops at crop+mirror and ships uint8 NHWC "
                            "(4x fewer h2d bytes than fp32), with "
                            "normalize/cast/layout fused into the step "
                            "program via normalize_symbol (explicit "
                            "device_augment=True/False always wins)"),
    "MXNET_IO_AUTO_SHARD": (_BOOL, True, "honored",
                            "an EXPLICIT num_parts='auto' on RecordIO-"
                            "backed iterators splits the record set by "
                            "this process's (rank, world) — DMLC_RANK/"
                            "DMLC_NUM_WORKER or the jax process grid — "
                            "re-resolved at every reset(), so "
                            "shrink-and-resume re-shards on the epoch "
                            "fence; 0 forces even 'auto' to a single "
                            "part (unset num_parts NEVER shards: eval "
                            "iterators must score the full set)"),
    # -- unified telemetry plane (obs/) --------------------------------------
    "MXNET_OBS_TRACE": (str, "", "honored",
                        "obs/trace.py: shared span JSONL file enabling "
                        "cross-process distributed tracing — every "
                        "process of a run (router, subprocess workers, "
                        "host daemons, parameter servers) appends its "
                        "finished spans there (O_APPEND line-atomic); "
                        "tools/mxtrace.py merges the file into ONE "
                        "Perfetto-loadable chrome trace with "
                        "cross-process flow arrows"),
    "MXNET_OBS_TRACE_BUFFER": (int, 65536, "honored",
                               "in-memory span buffer cap per process "
                               "(drop-oldest past it, counted in the "
                               "'trace.dropped' metric); spans "
                               "auto-flush to the shared file in "
                               "batches and at exit"),
    "MXNET_OBS_METRICS": (_BOOL, True, "honored",
                          "obs/metrics.py: invoke registered stats() "
                          "producers on scrape — off, collect() "
                          "returns raw instruments only (the paranoid "
                          "hot-path escape hatch; the 'metrics' "
                          "transport frame itself always answers)"),
    "MXNET_PROFILER_MAX_EVENTS": (int, 250000, "honored",
                                  "profiler.py in-memory custom-event "
                                  "buffer cap: a long supervised run "
                                  "with MXNET_PROFILER=1 drops the "
                                  "OLDEST events past it instead of "
                                  "exhausting host memory; drops are "
                                  "counted and surfaced as the "
                                  "'profiler.dropped_events' metric"),
    # -- sharded sparse embeddings (embedding/) ------------------------------
    "MXNET_EMBED_PARTITION": (str, "range", "honored",
                              "embedding/sharded.py row-partition rule: "
                              "'range' gives each shard one contiguous "
                              "row interval (reference ps-lite value "
                              "ranges), 'hash' spreads rows by a stable "
                              "integer mix of the row id (skew-resistant "
                              "for power-law id traffic)"),
    "MXNET_EMBED_CACHE_ROWS": (int, 4096, "honored",
                               "device-resident hot-row cache capacity "
                               "in rows per ShardedEmbedding (LRU over "
                               "row ids; 0 disables the cache and every "
                               "lookup pulls from its shard)"),
    "MXNET_EMBED_HBM_BUDGET_MB": (int, 64, "honored",
                                  "modeled single-device HBM budget for "
                                  "the embedding tier: ShardedEmbedding "
                                  "refuses to densify a table over it"),
    "MXNET_EMBED_PULL_CHUNK": (int, 65536, "honored",
                               "rows per embed_pull request when "
                               "streaming a whole shard back (checkpoint "
                               "capture / serving warm-up) so one reply "
                               "never materializes a table-sized frame"),
    "MXNET_EMBED_BREAKER_THRESHOLD": (int, 2, "honored",
                                      "consecutive exhausted-retry "
                                      "failures before an embedding "
                                      "shard is declared lost "
                                      "(ServerLostError naming the "
                                      "shard and its row range)"),
    "MXNET_EMBED_BREAKER_RESET_S": (float, 30.0, "honored",
                                    "open->half-open window of the "
                                    "per-shard embedding circuit "
                                    "breaker"),
}

_warned = set()


def get(name, default=None):
    """Read a knob with its registered parser; single read path."""
    if name not in KNOBS:
        raise KeyError(f"unknown config knob {name}; register it in "
                       "config.KNOBS")
    typ, reg_default, status, _ = KNOBS[name]
    raw = os.environ.get(name)
    if raw is None:
        return default if default is not None else reg_default
    if status == "subsumed" and name not in _warned:
        _warned.add(name)
        _LOG.debug("%s is set but subsumed by the XLA/TPU design; ignored",
                   name)
    try:
        return typ(raw)
    except (TypeError, ValueError):
        _LOG.warning("could not parse %s=%r; using default", name, raw)
        return default if default is not None else reg_default


def warn_unknown():
    """Flag MXNET_* env vars that match no registered knob (typo guard)."""
    unknown = []
    for key in os.environ:
        if key.startswith("MXNET_") and key not in KNOBS \
                and key not in _warned:
            _warned.add(key)
            unknown.append(key)
            _LOG.warning("environment variable %s matches no known knob "
                         "(typo? see config.KNOBS)", key)
    return unknown


def apply_startup_knobs():
    """Knobs that act at import time."""
    omp = get("MXNET_OMP_MAX_THREADS")
    if omp:
        os.environ.setdefault("OMP_NUM_THREADS", str(omp))
    if get("MXNET_FORCE_F32_MATMUL"):
        import jax
        jax.config.update("jax_default_matmul_precision", "highest")
    if get("MXNET_PROFILER_AUTOSTART"):
        from . import profiler
        try:
            profiler.set_state("run")
        except Exception:
            pass
    warn_unknown()
