"""Share of the window's expert assignments that the routing bias moved:
``lakesoul_train_moe_assignments_total{kind="bias_moved"}`` over
``{kind="all"}`` (``models/train.py``; ``parallel/moe.py:
route_sigmoid_top_k`` counts an assignment whose expert is among the top k of
``score + bias`` and not of ``score``), deltas over the window.  It says how
hard the bias steers: 0 where selection and weights agree (a zero bias, or a
family without one, whose series stays at 0), and every point is load the
bias took from the experts the scores prefer.  A program without the series
gives nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_moe_assignments_total"
MOVED = f'{COUNTER}{{kind="bias_moved"}}'


def read(sample):
    every = family_sum(sample["counters"], COUNTER, kind="all")
    if not every or MOVED not in sample["counters"]:
        return None
    return 100.0 * sample["counters"][MOVED] / every
