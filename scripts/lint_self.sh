#!/usr/bin/env bash
# Self-lint: run the program verifier over the shipped demo configs,
# audit op-registry metadata coverage against the checked-in baseline,
# and (when available) run ruff over the analysis package itself.
# Kept green by tests/test_lint_tooling.py in tier-1.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
PADDLE="python scripts/paddle"

echo "== paddle lint: demo/book configs"
for conf in demos/mnist_v1/trainer_config.py \
            demos/quick_start/trainer_config.py \
            demos/sequence_tagging/trainer_config.py \
            demos/traffic_prediction/trainer_config.py; do
    echo "-- $conf"
    $PADDLE lint "$conf"
done

echo "== paddle lint --optimize: rewrite pipeline dry-run over demo configs"
# the pipeline must leave every demo verifier-clean post-rewrite
# (exit 1 on any error diagnostic); covers the v1 trainer path
# (seq2seq, with control-flow sub-blocks the donation analyzer must
# hold) and the serving MLP the replica pool serves
$PADDLE lint --optimize demos/seq2seq/trainer_config.py
$PADDLE lint --optimize demos/serving_mlp/infer_config.py \
    --feed=x --fetch=prediction

echo "== paddle lint: registry metadata audit"
$PADDLE lint --audit-registry

echo "== registry ratchet: baseline gap must not regress"
python - <<'EOF'
import json
doc = json.load(open("paddle_tpu/analysis/registry_baseline.json"))
total = sum(len(v) for v in doc.values())
LIMIT = 80  # ratchet: only lower this, never raise it
assert total <= LIMIT, (
    f"registry baseline gap {total} > {LIMIT}: new/changed ops must "
    "ship infer_shape rules and input slots instead of growing the "
    "baseline (paddle_tpu/analysis/registry_audit.py)")
print(f"registry gap {total} <= {LIMIT}")
EOF

echo "== paddle stats: telemetry registry smoke"
# the observability surface must at least import + render cleanly
$PADDLE stats --json > /dev/null
$PADDLE stats > /dev/null

echo "== ruff: analysis + observability + distributed fault-tolerance + serving + decode + aot"
if command -v ruff >/dev/null 2>&1; then
    ruff check paddle_tpu/analysis/ paddle_tpu/observability/ \
        paddle_tpu/distributed/elastic.py paddle_tpu/distributed/retry.py \
        paddle_tpu/serving/ paddle_tpu/decode/ paddle_tpu/aot/
else
    echo "ruff not installed; skipping style pass"
fi

echo "== paddle compile: AOT artifact round trip (export -> boot -> parity)"
# exports a throwaway MLP, boots one server cold-JIT and one from the
# artifacts, and asserts a pure aot boot with byte-identical /predict
$PADDLE compile --smoke

echo "lint_self OK"
