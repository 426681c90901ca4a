#!/usr/bin/env bash
# Static analysis gate (see README "Static analysis"):
#
#   1. schema freshness — the committed event registry
#      (sparknet_tpu/obs/event_schema.py) must match what the repo
#      actually emits, or SPK401/402 are checking against stale truth
#   2. `sparknet lint --strict` over the package source with the
#      committed baseline: exits non-zero on ANY non-baselined
#      finding, stale baseline entry, or entry without a written
#      justification
#   3. relaxed per-tree passes: tests/ under the @tests profile
#      (parse + file-protocol + exit-code rules), scripts/ and
#      experiments/ under @tools (those plus the JAX host-sync
#      hazards)
#
# Every pass shares the content-hash result cache and a small worker
# pool. When $LINT_JSON_OUT is set, the strict pass's findings are
# also written there as JSON (CI uploads it as an artifact).
# jax-free: runs on any checkout, no accelerator stack needed.
set -euo pipefail
cd "$(dirname "$0")/.."

JOBS="${LINT_JOBS:-4}"

# 1. event-schema freshness: regenerate and diff
python -m sparknet_tpu lint --write-event-schema --root . >/dev/null
if ! git diff --quiet -- sparknet_tpu/obs/event_schema.py; then
    echo "lint.sh: sparknet_tpu/obs/event_schema.py is stale —" \
         "commit the regenerated file" >&2
    git --no-pager diff -- sparknet_tpu/obs/event_schema.py >&2
    exit 1
fi

# 2. the strict, baseline-gated package pass
if [ -n "${LINT_JSON_OUT:-}" ]; then
    python -m sparknet_tpu lint --json \
        --baseline .sparknet-lint-baseline.json \
        --root . sparknet_tpu > "$LINT_JSON_OUT" || true
fi
python -m sparknet_tpu lint --strict --cache --jobs "$JOBS" \
    --baseline .sparknet-lint-baseline.json \
    --root . sparknet_tpu

# 3. donation guard: SPK105 (missing buffer donation on an update jit)
#    must stay at ZERO findings repo-wide — every solver family donates
#    params/state/history, and new code keeps it that way. No baseline:
#    a single regression fails CI. (tests/fixtures holds the rule's own
#    intentional positive and is excluded everywhere.)
python -m sparknet_tpu lint --strict --cache --jobs "$JOBS" \
    --select SPK105 --exclude fixtures \
    --root . sparknet_tpu tests scripts experiments

# 4. relaxed per-tree profiles (the shared baseline stays empty)
python -m sparknet_tpu lint --strict --cache --jobs "$JOBS" \
    --select @tests --exclude fixtures \
    --baseline .sparknet-lint-baseline.json \
    --root . tests
python -m sparknet_tpu lint --strict --cache --jobs "$JOBS" \
    --select @tools \
    --baseline .sparknet-lint-baseline.json \
    --root . scripts experiments
