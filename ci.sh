#!/usr/bin/env bash
# CI entrypoint: deps + tier-1 tests + `datax check` over the shipped
# examples + headless runs of the examples + the docs link/fence check +
# the API surface check.  Speed is measured on the chip by `bench/`
# (BENCHMARK.json), not here.  Runs on two matrix
# legs (.github/workflows/ci.yml): full deps, and minimal deps via
# CI_SKIP_INSTALL=1 (no jax/zstandard/hypothesis) to exercise every
# graceful-degradation path.
set -euo pipefail
cd "$(dirname "$0")"

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Best-effort dependency install; the repo degrades gracefully without the
# optional ones (jax -> host-composed fusion, zstandard -> zlib fallback,
# hypothesis -> skipped tests).
if [ "${CI_SKIP_INSTALL:-0}" != "1" ]; then
    python -m pip install --quiet -r requirements.txt \
        || echo "ci.sh: pip install failed (offline?); using preinstalled deps"
fi

echo "== tier-1 tests =="
python -m pytest -x -q

echo "== datax check (static dataflow analysis) =="
# every shipped example must be free of error-severity diagnostics (the CLI
# exits 1 on any surviving error; vetted exceptions use
# `# datax: ignore[DXnnn] reason` pragmas) — both matrix legs
python tools/datax_check.py examples/quickstart.py
python tools/datax_check.py examples/fever_screening.py
python tools/datax_check.py examples/stream_reuse.py
python tools/datax_check.py examples/replay_corpus.py

echo "== examples (headless) =="
python examples/quickstart.py
python examples/fever_screening.py
python examples/stream_reuse.py
python examples/replay_corpus.py
# the LM examples (now v2 fluent-DSL apps) need jax — full-deps leg only
if python -c "import jax" 2>/dev/null; then
    echo "== examples (headless, jax) =="
    python tools/datax_check.py examples/serve_lm.py
    python tools/datax_check.py examples/train_lm.py
    python examples/serve_lm.py --requests 6 --slots 3
    python examples/train_lm.py --steps 4 --batch 4 --seq 64 \
        --workdir "$(mktemp -d)"
fi

echo "== docs check =="
# docs/ + README relative links must resolve; python fences in docs/*.md
# must compile (stdlib only — both matrix legs, also a standalone CI job)
python tools/check_docs.py

echo "== api surface check =="
# repro.core's public names + signatures must match the committed snapshot
# (docs/api-surface.txt); intentional changes rerun with --update and commit
python tools/check_api.py

echo "ci.sh: OK"
