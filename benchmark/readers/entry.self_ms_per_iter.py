"""Mean self time of the program's ``lgbm/train/iteration`` span in the traced
window: the iteration less its children (prepare, step dispatch, record, queue
drain, stop check), i.e. the program's own Python an iteration that no child
span accounts for."""
import progtrace


def read(facts):
    red = progtrace.of(facts)
    mean = red and red["span_means"].get("train/iteration")
    return mean["self_mean_ms"] if mean else None
