"""``keyless_reduce_pct`` reads the program's two counters and finds nothing
on a program without them (the parent of PR 29)."""

import harness


def _run(*counters):
    return {"records": [{"answered": True, "counters": c} for c in counters]}


def test_share_of_keyless_update_batches_that_were_reduced():
    read = harness.load_reader("layer_metrics", "keyless_reduce_pct").read
    assert read(_run({"dispatchCount": 2})) is None            # the parent
    assert read(_run({"keylessAggBatches": 0,
                      "keylessUpdateBatches": 0})) is None     # keyed only
    assert read(_run({"keylessAggBatches": 6, "keylessUpdateBatches": 6},
                     {"keylessAggBatches": 6,
                      "keylessUpdateBatches": 6})) == 100.0
    assert read(_run({"keylessAggBatches": 0, "keylessUpdateBatches": 6},
                     {"keylessAggBatches": 6,
                      "keylessUpdateBatches": 6})) == 50.0
    unanswered = {"answered": False, "counters": {
        "keylessAggBatches": 0, "keylessUpdateBatches": 6}}
    run = _run({"keylessAggBatches": 3, "keylessUpdateBatches": 6})
    run["records"].append(unanswered)
    assert read(run) == 50.0
