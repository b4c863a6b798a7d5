"""Score-map work differential attention's kernels run over the work its
pairs require: ``lakesoul_train_attn_pair_key_tiles_total{kind="run"}`` over
``{kind="required"}`` (``models/train.py``; host integers off
``models/attention.py: paired_attention``, known when the step is traced:
the (query tile, key tile) steps the attention kernels' lists hold over the
paired calls' rows and key-value heads, and the steps two score maps a head
pair require, summed over the window's steps), deltas over the window.  100
where every map is computed once (a key-value head a map, beside the pair's
whole value); 200 where a map meets the two halves of its value as two
key-value heads with the same key.  A change of how the pairs are laid out
for the kernels moves it.  A program without the series (every program before
PR 51), or a family without head pairs (0), gives nothing."""

COUNTER = "lakesoul_train_attn_pair_key_tiles_total"


def read(sample):
    counters = sample["counters"]
    run = counters.get(f'{COUNTER}{{kind="run"}}')
    required = counters.get(f'{COUNTER}{{kind="required"}}')
    if not run or not required:
        return None
    return 100.0 * run / required
