"""Full-data passes an iteration: launches of the ``hist`` group's kernels
(the traffic file's ``kernels``) on the device in the traced window, over the
iterations traced. Times ``kernel.ms_per_pass`` it is the group's device time
an iteration."""
import progtrace


def read(facts):
    red = progtrace.of(facts)
    n = red and red["group_launches"].get("hist")
    if not n or not facts["done"]:
        return None
    return n / facts["done"]
