"""How far the fullest shard stands over the mean one in the rows its wave
passes put through the dot: chips x ``hist/rows_dotted_max_shard`` (the
``pmax`` over the shards, a pass at a time) over ``hist/rows_dotted`` (their
sum). 1.0 is shards that reach every sum together; at 1.1 three chips wait a
tenth of the dot's time for the fourth. None where the program has no such
counter (a serial learner has no shards)."""


def read(facts):
    from lightgbm_tpu.obs import registry as obs
    c = dict(obs.default_registry().counter_items())
    chips = int(facts.get("chips", 1))
    if (chips < 2 or not c.get("hist/rows_dotted")
            or "hist/rows_dotted_max_shard" not in c):
        return None
    return chips * c["hist/rows_dotted_max_shard"] / c["hist/rows_dotted"]
