"""Share of a step's softmax-attention layer-rows whose attention kernels'
operands (the query and key normed over a head, turned by position, the query
scaled, all three laid out heads first) the operand kernels made in one pass:
``lakesoul_train_attn_operand_rows_total{path="kernel"}`` over ``{path="kernel"}
+ {path="xla"}`` (``models/train.py``; host integers off ``models/causal_lm.py:
mixer_counts``, one abstract trace of each mixer when the step is traced,
summed over the window's steps, rows and attention layers), deltas over the
window.  100 where every mixer's shape is one ``_operand_tiles`` takes (a head
of whole 128-lane tiles, positions over the whole head or none); 0 where none
is, and the ``jnp`` lines run.  A change of the rule moves it.  A program
without the series, or with no such mixer (both 0: latent attention, an
encoder), gives nothing."""

COUNTER = "lakesoul_train_attn_operand_rows_total"


def read(sample):
    counters = sample["counters"]
    kernel = counters.get(f'{COUNTER}{{path="kernel"}}')
    xla = counters.get(f'{COUNTER}{{path="xla"}}')
    if kernel is None or xla is None or not kernel + xla:
        return None
    return 100.0 * kernel / (kernel + xla)
