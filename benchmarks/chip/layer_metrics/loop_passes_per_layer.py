"""Passes a looped model's step runs of each layer it holds:
``lakesoul_train_loop_layer_passes_total{kind="run"}`` over ``{kind="layers"}``
(``models/train.py: make_lm_train_step``; host integers off
``models/causal_lm.py: loop_hidden``, rows x layers x passes and rows x layers,
summed over the window's steps), deltas over the window.  4.0 at
``total_ut_steps`` 4; a program that skips a pass or exits early moves it.  A
program without the series, or a family that does not loop (both 0), gives
nothing."""

COUNTER = "lakesoul_train_loop_layer_passes_total"


def read(sample):
    counters = sample["counters"]
    layers = counters.get(f'{COUNTER}{{kind="layers"}}')
    run = counters.get(f'{COUNTER}{{kind="run"}}')
    if not layers or run is None:
        return None
    return run / layers
