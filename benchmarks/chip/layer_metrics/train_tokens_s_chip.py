"""Tokens a second and chip through the train step: the program's own count
(``lakesoul_train_tokens_total``, ``models/train.py``: positions of the batches
the steps took), delta over the window's seconds and chips.  Rows a second
times the row length, from the program's side.  A program without the counter
gives nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_tokens_total"


def read(sample):
    tokens = family_sum(sample["counters"], COUNTER)
    if not tokens:
        return None
    return tokens / sample["window_s"] / sample["chips"]
