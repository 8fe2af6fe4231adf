"""Device-busy time inside the traced iterations over their number."""


def read(facts):
    tr = facts["trace"]
    if not tr["busy_s"] or not facts["done"]:
        return None
    return 1e3 * tr["busy_s"] / facts["done"]
