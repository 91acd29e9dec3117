#!/usr/bin/env bash
# Tier-1, as the driver runs it after every PR (its `commands`, in
# /root/TESTS_LAST_RUN.json, are the authority where they differ): six
# xdist workers, a file a worker, 1,470 s.  The last lines give the
# count of passes the floor is held against and the workers lost.
cd "$(dirname "$0")/.."
set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); exit $rc
