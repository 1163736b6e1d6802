#!/usr/bin/env bash
# Registry smoke (make registry-smoke, docs/registry.md): a 2-process
# sharded warm against a shared artifact registry, then a FRESH process
# with an empty local TDX_CACHE_DIR that must materialize the model with
# zero local compiles — every program a registry fetch hit feeding a
# local compile-cache hit — and land bitwise-equal to the no-registry
# path.  CPU-only, bounded, exercises real process boundaries (the
# in-process equivalents live in tests/test_registry.py).
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export TDX_CACHE_MIN_COMPILE_S=0

TMP=$(mktemp -d /tmp/tdx_registry_smoke.XXXXXX)
trap 'rm -rf "$TMP"' EXIT
REG="$TMP/registry"

echo "== sharded warm: 2 concurrent worker processes =="
python tools/warm_cache.py --model demo --cache-dir "$TMP/host0" \
    --registry-dir "$REG" --hosts 2 --host-id 0 --steal-after 300 \
    > "$TMP/host0.json" 2> "$TMP/host0.log" &
P0=$!
python tools/warm_cache.py --model demo --cache-dir "$TMP/host1" \
    --registry-dir "$REG" --hosts 2 --host-id 1 --steal-after 300 \
    > "$TMP/host1.json" 2> "$TMP/host1.log" &
P1=$!
wait $P0 || { echo "host0 warm failed"; cat "$TMP/host0.log"; exit 1; }
wait $P1 || { echo "host1 warm failed"; cat "$TMP/host1.log"; exit 1; }
grep '^warm:' "$TMP/host0.log" | sed 's/^/  host0 /'
grep '^warm:' "$TMP/host1.log" | sed 's/^/  host1 /'

echo "== verifying disjoint compile shards =="
python - "$TMP/host0.json" "$TMP/host1.json" <<'EOF'
import json, sys
reports = []
for path in sys.argv[1:]:
    with open(path) as f:
        reports.append(json.loads(f.read().strip().splitlines()[-1]))
compiled = []
for host, rep in enumerate(reports):
    own = {r["program"] for r in rep["program_reports"]
           if r["outcome"] in ("published", "compiled", "stolen")}
    assert not rep["unwarmed"], (host, rep["unwarmed"])
    compiled.append(own)
    print(f"  host{host} compiled: {sorted(own)}")
overlap = compiled[0] & compiled[1]
assert not overlap, f"hosts compiled overlapping programs: {overlap}"
union = compiled[0] | compiled[1]
all_programs = {r["program"] for rep in reports
                for r in rep["program_reports"]}
assert union == all_programs, (union, all_programs)
print(f"  OK: {len(all_programs)} programs, disjoint shards, full cover")
EOF

echo "== fresh-process cold start: empty local cache, all registry hits =="
TDX_CACHE_DIR="$TMP/fresh" TDX_REGISTRY_DIR="$REG" \
    TDX_METRICS_PATH="$TMP/fresh.jsonl" python - <<'EOF'
import json, os
import numpy as np
import torch
from torchdistx_tpu.deferred_init import deferred_init
from torchdistx_tpu.jax_bridge import materialize_module_jax
from torchdistx_tpu import observe

widths = [32 + 8 * i for i in range(12)]

class Demo(torch.nn.Module):  # tools/warm_cache.py's demo model
    def __init__(self):
        super().__init__()
        self.layers = torch.nn.ModuleList(
            torch.nn.Linear(widths[i], widths[(i + 1) % len(widths)])
            for i in range(len(widths)))

params = materialize_module_jax(deferred_init(Demo), seed=0)
snap = {r["name"]: r["value"] for r in observe.counters().snapshot()
        if r["type"] == "counter"}
n_hit = snap.get("tdx.jax.compile_cache_hit", 0)
n_miss = snap.get("tdx.jax.compile_cache_miss", 0)
r_hit = snap.get("tdx.registry.fetch_hit", 0)
assert n_miss == 0, f"cold start paid {n_miss} local compiles"
assert n_hit > 0 and r_hit == n_hit, (n_hit, r_hit)

# Bitwise parity vs the no-registry path.
import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import compile_service
compile_service.reset_cache_binding()
with tdx_config.override(cache_dir=None, registry_dir=None,
                         materialize_pipeline="off"):
    base = materialize_module_jax(deferred_init(Demo), seed=0)
for k in base:
    assert np.array_equal(np.asarray(base[k]), np.asarray(params[k])), k
print(f"  OK: {int(n_hit)} programs, 0 local compiles, "
      f"{int(r_hit)} registry fetches, bitwise equal")
EOF

echo "registry-smoke OK"
