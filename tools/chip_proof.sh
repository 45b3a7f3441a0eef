#!/bin/sh
# The proof a PR gives before it ends: chip_smoke.py run from exactly the
# files git would commit, and the compile cache placed from outside.
#
# Here, where .git is (the chip machine's copy has none):
#     git add -A
#     rm -rf _proof && mkdir _proof
#     git archive "$(git write-tree)" | tar -x -C _proof      # _proof/ is gitignored
# There, from the repo root:
#     chiprun --timeout 1800 -- sh tools/chip_proof.sh          # the smoke from the archive
#     chiprun --timeout 2700 -- sh tools/chip_proof.sh placed   # ... and again with the cache placed
#
# Run 1 leaves JAX_COMPILATION_CACHE_DIR unset: the programs must land in
# _proof/.jax_cache.  Run 2 (argument "placed") sets it to a directory
# outside the checkout: that directory must fill, _proof/.jax_cache must
# keep its file count, and the first server must hit nothing (a new
# directory holds no program).  The exit code is non-zero if a run failed
# or a count is not what this says.
set -u
cd "$(dirname "$0")/../_proof" || { echo "no _proof/ tree: make it first (see the head of $0)"; exit 2; }

count() { find "$1" -type f 2>/dev/null | wc -l; }

echo "== files in proof tree: $(count .); .so: $(find . -name '*.so' | wc -l);" \
     "pycache: $(find . -name __pycache__ | wc -l); .jax_cache: $(count .jax_cache)"
[ "$(find . -name '*.so' -o -name __pycache__ | wc -l)" -eq 0 ] && [ ! -e .jax_cache ] \
    || { echo "the tree holds what git would not commit"; exit 2; }

unset JAX_COMPILATION_CACHE_DIR
python3 chip_smoke.py
rc=$?
own=$(count .jax_cache)
echo "== run 1 rc=$rc; <checkout>/.jax_cache files: $own"
[ "$rc" -eq 0 ] && [ "$own" -gt 0 ] || exit 1

[ "${1:-}" = placed ] || exit 0
placed=/tmp/placed-cache
rm -rf "$placed"
JAX_COMPILATION_CACHE_DIR=$placed python3 chip_smoke.py
rc=$?
echo "== run 2 (JAX_COMPILATION_CACHE_DIR=$placed) rc=$rc;" \
     "<checkout>/.jax_cache files: $(count .jax_cache) (was $own); $placed files: $(count "$placed")"
[ "$rc" -eq 0 ] && [ "$(count .jax_cache)" -eq "$own" ] && [ "$(count "$placed")" -gt 0 ]
