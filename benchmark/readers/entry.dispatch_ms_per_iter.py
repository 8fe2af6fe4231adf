"""Mean length of the program's ``lgbm/train/step_dispatch`` span in the traced
window: the host inside the jitted step's call, an iteration
(``lightgbm_tpu/models/gbdt.py`` ``_train_one_iter_inner``)."""
import progtrace


def read(facts):
    red = progtrace.of(facts)
    mean = red and red["span_means"].get("train/step_dispatch")
    return mean["mean_ms"] if mean else None
