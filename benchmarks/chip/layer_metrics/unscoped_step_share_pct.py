"""Share of the train step's device time that the step's scope map charges to
no ``lakesoul.lm.*`` scope (``chipbench/scopes.py: UNATTRIBUTED``): operations
the compiler inserts after the program's metadata is gone (copies between
memory spaces, layout copies, converts) and whatever the program still writes
under no ``jax.named_scope``.  The gauge of the other step shares: with it they
add up to 100, and a program that drops a scope shows here.  A run without the
scope map, or without an execution of the step, gives nothing."""

from chipbench import scopes


def read(sample):
    result = scopes.of_run(sample)
    if result is None or not result["step_s"]:
        return None
    return 100.0 * result["seconds"].get(scopes.UNATTRIBUTED, 0.0) / result["step_s"]
