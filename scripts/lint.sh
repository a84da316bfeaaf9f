#!/usr/bin/env bash
# graftlint wrapper: human output to the terminal, machine-readable
# findings recorded to LINT.json (counts per rule + every finding with
# its fingerprint). Exit code is graftlint's: 0 clean, 1 errors.
#
#   scripts/lint.sh                # analyze the package
#   scripts/lint.sh path/to.py     # analyze specific files/dirs
#   LINT_OUT=/tmp/l.json scripts/lint.sh
set -u
cd "$(dirname "$0")/.."

targets=("$@")
default_scope=0
if [ ${#targets[@]} -eq 0 ]; then
    targets=(chainermn_tpu/)
    default_scope=1
fi
out="${LINT_OUT:-LINT.json}"

python -m chainermn_tpu.analysis --json "${targets[@]}" > "$out"
status=$?

python -m chainermn_tpu.analysis "${targets[@]}"
echo "findings record: $out"

# cross-check the runtime sanitizer's observed lock-order graph against
# the static one (observed must be a subset). SANITIZER.json is dumped
# by the serving/fleet/dataflow tier-1 suites; only meaningful against
# the default full-package scope.
if [ "$default_scope" -eq 1 ] && [ -f SANITIZER.json ]; then
    python -m chainermn_tpu.analysis chainermn_tpu/ \
        --runtime-report SANITIZER.json || status=1
fi

exit $status
