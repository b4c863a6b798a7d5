"""Layer-rows a step that read a state an EARLIER layer published:
``lakesoul_train_shared_state_reads_total`` (``models/train.py``; host
integers off ``models/causal_lm.py: _attention_counts``: the rows of every
layer whose mixer takes another layer's state, ``cfg.shares``) over the
window's steps.  Rows a step x the second decoder's layers held (4 at 2 rows
and one gated memory unit and one cross layer); a change that gives those
layers a state of their own, or computes the sources twice, moves it.  A
program without the series, or a family that shares nothing (0), gives
nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_shared_state_reads_total"


def read(sample):
    reads = family_sum(sample["counters"], COUNTER)
    if not reads or not sample.get("steps"):
        return None
    return reads / sample["steps"]
