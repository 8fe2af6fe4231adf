"""Host time to issue one iteration: the benchmark's own span around each
``Booster.update()`` call in the window."""


def read(facts):
    spans, (t0, _) = facts["spans"], facts["t_window"]
    names = ("update_issue", "update_issue+stop_check")
    n = sum(spans.count(k, t0) for k in names)
    if not n:
        return None
    return 1e3 * sum(spans.total(k, t0) for k in names) / n
