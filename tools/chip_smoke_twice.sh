#!/bin/bash
# chip_smoke.py twice in ONE chip command, cold compile cache then warm, and a
# comparison of what the two runs computed: equal losses and tokens is the
# donated-buffers-plus-persistent-cache-hits case (parallel/trainer.py
# _compile).  This parent never touches JAX, so each run gets the chip.
#
#   chiprun --timeout 2400 -- bash tools/chip_smoke_twice.sh [--chips 4]
#
# Full phase lines land in chiprun_out/{cold,warm}.jsonl.
mkdir -p chiprun_out
for r in cold warm; do
  s=$(date +%s)
  python3 chip_smoke.py "$@" > chiprun_out/$r.jsonl 2> chiprun_out/$r.err
  echo "$r rc=$? seconds=$(( $(date +%s) - s ))"
  grep -v "^WARNING\|^W0\|^I0" chiprun_out/$r.err | tail -15
done
python3 - <<'PY'
import json
import sys


def load(path):
    return [json.loads(line) for line in open(path) if line.startswith("{")]


def computed(recs):
    """Every loss list and token list of a run, wherever its phase put them."""
    out = []

    def walk(x):
        if isinstance(x, list):
            for v in x:
                walk(v)
        elif isinstance(x, dict):
            for k, v in sorted(x.items()):
                if k in ("losses", "new_tokens"):
                    out.append((k, v))
                else:
                    walk(v)
    walk(recs)
    return out


cold, warm = load("chiprun_out/cold.jsonl"), load("chiprun_out/warm.jsonl")
for name, recs in (("cold", cold), ("warm", warm)):
    for rec in recs:
        print(name, json.dumps(rec)[:6000])
a, b = computed(cold), computed(warm)
same = bool(a) and a == b and all(r and r[-1].get("ok") for r in (cold, warm))
print("COLD_WARM_EQUAL", bool(same))
sys.exit(0 if same else 1)
PY
