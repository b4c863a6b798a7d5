"""Share of a step's attention layer-rows whose flash kernels wrote the output
where the output projection reads it, token-major ``[T, heads x D]``, through
their own block specs (and read its cotangent there in the backward pass):
``lakesoul_train_attn_output_rows_total{layout="tokens"}`` over
``{layout="tokens"} + {layout="heads"}`` (``models/train.py``; host integers
off ``models/causal_lm.py: mixer_counts``, one abstract trace of each mixer
when the step is traced, summed over the window's steps, rows and attention
layers), deltas over the window.  100 where every mixer's head is whole
128-lane tiles (``_token_major``: no layout copy stands between the kernels
and ``w_o``); 0 where none is (a head of 64: heads first, transposed after).
A change of the rule moves it.  A program without the series, or without
attention (an encoder with its own), gives nothing."""

COUNTER = "lakesoul_train_attn_output_rows_total"


def read(sample):
    counters = sample["counters"]
    tokens = counters.get(f'{COUNTER}{{layout="tokens"}}')
    heads = counters.get(f'{COUNTER}{{layout="heads"}}')
    if tokens is None or heads is None or not tokens + heads:
        return None
    return 100.0 * tokens / (tokens + heads)
