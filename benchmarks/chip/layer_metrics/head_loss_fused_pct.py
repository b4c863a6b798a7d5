"""Share of the rows a step hands the head's tile loop (loss and gradients,
over every loss the step sums) whose tiles ran the fused body: ONE Pallas
kernel from the head's float32 logits to each row's NLL and the logits'
cotangent, between ``jax.vjp`` of the head and its pull-back:
``lakesoul_train_loss_rows_total{body="fused"}`` over ``{body="fused"} +
{body="compiler"}`` (``models/train.py``; host integers off
``models/causal_lm.py: _head_nll``, known when the step is traced, summed over
the window's steps), deltas over the window.  100 where a tile of float32
logits is one the kernel takes (``models/loss_tile.py: tile_takes``: 48 MiB
or more, all five LM cells' tiles); 0 where ``jax.nn.log_softmax`` and
autodiff run (a tile smaller than any the kernel is measured at).  A change of the rule, or of the tile's rows,
moves it.  A program without the series (the masked-LM steps, which pass no
body; any program before PR 47) gives nothing."""

COUNTER = "lakesoul_train_loss_rows_total"


def read(sample):
    counters = sample["counters"]
    fused = counters.get(f'{COUNTER}{{body="fused"}}')
    compiler = counters.get(f'{COUNTER}{{body="compiler"}}')
    if fused is None or compiler is None or not fused + compiler:
        return None
    return 100.0 * fused / (fused + compiler)
