"""Share of the traced window a core's own operation stream spent in
collective operations (no compute runs on that core meanwhile), worst device."""


def read(sample):
    reduced = sample["trace"]
    if reduced is None or not reduced.get("window_s"):
        return None
    return 100.0 * reduced["collective_exposed_s_worst"] / reduced["window_s"]
