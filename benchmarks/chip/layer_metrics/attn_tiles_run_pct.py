"""Share of a causal tile list that the attention kernels' lists hold:
``lakesoul_train_attn_key_tiles_total{kind="run"}`` over ``{kind="causal"}``
(``models/train.py``; host integers off ``models/causal_lm.py:
key_tile_steps``, summed over the window's steps, rows, attention layers and
key-value heads), deltas over the window.  100 where no layer has a window;
61.2 where four layers of five run 280 of a causal list's 544 steps; a change
that stops skipping tiles, or changes the tile sizes, moves it.  A program
without the series, or whose shapes the kernels do not take (both 0), gives
nothing."""

COUNTER = "lakesoul_train_attn_key_tiles_total"


def read(sample):
    counters = sample["counters"]
    causal = counters.get(f'{COUNTER}{{kind="causal"}}')
    run = counters.get(f'{COUNTER}{{kind="run"}}')
    if not causal or run is None:
        return None
    return 100.0 * run / causal
