#!/usr/bin/env bash
# Tier-1 verification — the exact command from ROADMAP.md.  CI and
# humans run this one script so the gate can't drift from the docs.
set -o pipefail
cd "$(dirname "$0")/.."

# graftlint (static analysis gate): the ray_tpu/ AND tests/ trees must
# carry zero unsuppressed invariant violations against .graftlint.toml,
# with no stale baseline entries (--strict), inside a 30 s budget.  Runs
# first: it is the cheapest signal and failures are line-precise.  The
# JSON report feeds the one-line gate summary (checker/violation counts)
# and stays in /tmp/_graftlint.json for CI artifacts.
if ! timeout -k 5 30 python -m ray_tpu.devtools.lint ray_tpu tests --strict --json \
    > /tmp/_graftlint.json; then
  python - <<'EOF' 2>/dev/null || cat /tmp/_graftlint.json
import json
r = json.load(open("/tmp/_graftlint.json"))
for v in r["violations"]:
    if not v.get("suppressed_by"):
        print(f"{v['path']}:{v['line']}: {v['check']}: {v['message']}")
for v in r["parse_errors"]:
    print(f"{v['path']}:{v['line']}: {v['check']}: {v['message']}")
for e in r["unused_baseline"]:
    print(f"stale baseline entry: {e['check']} @ {e['path']}")
EOF
  echo "graftlint gate failed (see docs/static_analysis.md)"
  exit 1
fi
python - <<'EOF'
import json
r = json.load(open("/tmp/_graftlint.json"))
firing = {k: n for k, n in r["by_check"].items() if n}
print(
    f"GRAFTLINT_GATE checks={len(r['checks_run'])} files={r['files_checked']} "
    f"unsuppressed={r['unsuppressed']} suppressed={r['suppressed']} "
    f"cache_hits={r['cache']['hits']} elapsed={r['elapsed_s']}s"
    + (f" firing={firing}" if firing else "")
)
EOF

rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu \
  python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)

# Observability smoke (flight recorder end-to-end): local cluster, 10
# traced tasks, /metrics parses, /api/timeline shows a cross-process
# trace.  Skippable via RAY_TPU_SKIP_OBS_SMOKE=1.
if [ "${RAY_TPU_SKIP_OBS_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
      python scripts/observability_smoke.py; then
    echo "observability smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Dataplane trace smoke (trace-context propagation end-to-end): 2-raylet
# cluster, one traced serve call over the channel dataplane + one traced
# compiled-DAG execution across a socket edge — both come back as single
# connected traces spanning >=2 processes with zero orphan spans.
# Skippable via RAY_TPU_SKIP_DATAPLANE_SMOKE=1.
if [ "${RAY_TPU_SKIP_DATAPLANE_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 150 env JAX_PLATFORMS=cpu \
      python scripts/dataplane_trace_smoke.py; then
    echo "dataplane trace smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Drain smoke (graceful node drain end-to-end): 2-node local cluster,
# drain a node hosting a live actor + sole-copy object, assert the actor
# migrates, the object survives the kill, and util.state + /api/nodes
# show DRAINING -> DEAD.  Skippable via RAY_TPU_SKIP_DRAIN_SMOKE=1.
if [ "${RAY_TPU_SKIP_DRAIN_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 180 env JAX_PLATFORMS=cpu \
      python scripts/drain_smoke.py; then
    echo "drain smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Tenant smoke (multi-tenant job plane end-to-end): two tenants with
# unequal quotas under sustained task demand — usage converges on the
# quota split within 10% and never exceeds a quota persistently.
# Skippable via RAY_TPU_SKIP_TENANT_SMOKE=1.
if [ "${RAY_TPU_SKIP_TENANT_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 180 env JAX_PLATFORMS=cpu \
      python scripts/tenant_smoke.py; then
    echo "tenant smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Serve LLM smoke (inference serving plane end-to-end): tiny GPT-2
# behind serve.run, 24 concurrent token streams + one mid-stream cancel,
# assert all completions exact, KV block pool balanced to zero, and the
# continuous batcher actually batched.  Skippable via
# RAY_TPU_SKIP_SERVE_LLM_SMOKE=1.
if [ "${RAY_TPU_SKIP_SERVE_LLM_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 180 env JAX_PLATFORMS=cpu \
      python scripts/serve_llm_smoke.py; then
    echo "serve llm smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Serve overload smoke (overload armor end-to-end over HTTP): hostile
# tenant floods at many times its token-rate quota while a victim tenant
# streams interactively — assert 429s attributed to the hostile tenant
# only, victim TTFT bounded, KV pool balanced to zero.  Skippable via
# RAY_TPU_SKIP_SERVE_OVERLOAD_SMOKE=1.
if [ "${RAY_TPU_SKIP_SERVE_OVERLOAD_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 180 env JAX_PLATFORMS=cpu \
      python scripts/serve_overload_smoke.py; then
    echo "serve overload smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Compiled-DAG smoke (zero-copy dataplane end-to-end): 2-raylet cluster,
# 3-actor fan-out with one socket edge + shm rings, exact results over
# 200 executions, sub-ms local round-trip p50 (multicore), teardown
# reclaims tmpfs.  Skippable via RAY_TPU_SKIP_DAG_SMOKE=1.
if [ "${RAY_TPU_SKIP_DAG_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
      python scripts/compiled_dag_smoke.py; then
    echo "compiled dag smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Dataplane chaos smoke (self-healing dataplane end-to-end): compiled
# DAG with a cross-raylet socket edge + serve calls and token streams
# under a seeded chan:* chaos spec (mid-frame torn writes, abrupt
# socket drops, a serve ring close) — every result exact via epoch
# reattach / RPC fallback, zero leaked shm.  Skippable via
# RAY_TPU_SKIP_DATAPLANE_CHAOS_SMOKE=1.
if [ "${RAY_TPU_SKIP_DATAPLANE_CHAOS_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
      python scripts/dataplane_chaos_smoke.py; then
    echo "dataplane chaos smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Checkpoint chaos smoke (durable checkpoint plane end-to-end): a JAX
# training loop SIGKILLed mid-shard and pre-commit (seeded ckpt:*
# rules) with a bit-flipped shard at rest restarts every time from the
# last COMMITTED checkpoint with byte-exact loss/parameter parity,
# never adopts corrupted state, and leaves zero debris after retention
# GC.  Skippable via RAY_TPU_SKIP_CHECKPOINT_CHAOS_SMOKE=1.
if [ "${RAY_TPU_SKIP_CHECKPOINT_CHAOS_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 180 env JAX_PLATFORMS=cpu \
      python scripts/checkpoint_chaos_smoke.py; then
    echo "checkpoint chaos smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# RLlib async smoke (podracer streaming plane end-to-end): 2 streaming
# env runners + learner over real channels, fixed seed, reward parity
# vs the synchronous PPO path on CartPole, and the IMPALA-style async
# config clearing the same bar.  Skippable via RAY_TPU_SKIP_RLLIB_SMOKE=1.
if [ "${RAY_TPU_SKIP_RLLIB_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
      python scripts/rllib_async_smoke.py; then
    echo "rllib async smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Profiling smoke (bottleneck-attribution plane end-to-end): actor under
# load, attach the sampling profiler, assert a non-empty merged
# flamegraph with the workload visible and valid speedscope output.
# Skippable via RAY_TPU_SKIP_PROFILING_SMOKE=1.
if [ "${RAY_TPU_SKIP_PROFILING_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
      python scripts/profiling_smoke.py; then
    echo "profiling smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Sharded train smoke (GSPMD + MPMD planes end-to-end on CPU devices):
# batch x model mesh loss parity vs data parallel, per-shard checkpoint
# re-shard across a mesh resize, and a 2-stage pipeline over real
# channels matching single-process loss.  Skippable via
# RAY_TPU_SKIP_SHARDED_SMOKE=1.
if [ "${RAY_TPU_SKIP_SHARDED_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
      python scripts/sharded_train_smoke.py; then
    echo "sharded train smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Partition smoke (membership plane end-to-end): asymmetric-partition
# drill (net:node2->gcs:cut — dataplane stays up, silent node declared
# DEAD past dead_conn_open_factor, zombie write fenced typed+counted,
# raylet rejoins as a new incarnation) and gray-failure drill
# (net:...:slow — SUSPECT -> QUARANTINED, never false DEAD, readmitted
# after heal within the flap budget).  Skippable via
# RAY_TPU_SKIP_PARTITION_SMOKE=1.
if [ "${RAY_TPU_SKIP_PARTITION_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 240 env JAX_PLATFORMS=cpu \
      python scripts/partition_smoke.py; then
    echo "partition smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi

# Elastic smoke (resize-on-preemption end-to-end): 2-node local cluster,
# elastic JaxTrainer (min_workers=1), preempt one rank's node mid-run,
# assert shrink -> resume -> completion with zero failure charges and
# resize events/spans recorded.  Skippable via RAY_TPU_SKIP_ELASTIC_SMOKE=1.
if [ "${RAY_TPU_SKIP_ELASTIC_SMOKE:-0}" != "1" ]; then
  if ! timeout -k 10 180 env JAX_PLATFORMS=cpu \
      python scripts/elastic_smoke.py; then
    echo "elastic smoke step failed"
    [ "$rc" -eq 0 ] && rc=1
  fi
fi
exit $rc
