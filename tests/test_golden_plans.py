"""Golden-plan stability (the historical_plans discipline).

Replans a representative slice of the QTT corpus and diffs the serialized
QueryPlan JSON against the committed golden_plans/ tree.  A failure here
means the plan format or the planner's output changed: that is an upgrade-
compatibility decision — if intentional, regenerate with
``python scripts/gen_golden_plans.py`` and review the diff."""

import os

import pytest

from ksql_tpu.tools.golden_plans import (
    BREADTH_FILES,
    GOLDEN_DIR,
    QTT_DIR,
    diff_file,
)

# breadth over the plan surface: projections, aggregates, all join flavors,
# windows, partition-by, suppress, serde features — shared with the static
# backend-classification snapshot (tests/test_analysis.py)
FILES = BREADTH_FILES


@pytest.mark.skipif(
    not os.path.isdir(QTT_DIR),
    reason="replanning needs the QTT corpus, which is external to this repo "
    f"(ksqlDB's query-validation-tests, expected at {QTT_DIR})",
)
@pytest.mark.parametrize("fname", FILES)
def test_golden_plans_stable(fname):
    assert os.path.exists(os.path.join(GOLDEN_DIR, fname)), (
        "golden corpus missing — run scripts/gen_golden_plans.py"
    )
    diffs = diff_file(fname)
    assert not diffs, diffs[:10]


def test_corpus_is_substantial():
    import json

    total = 0
    for f in os.listdir(GOLDEN_DIR):
        with open(os.path.join(GOLDEN_DIR, f)) as fh:
            total += len(json.load(fh))
    assert total >= 1500, total
