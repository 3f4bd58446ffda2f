"""prefix_hit_rate.serve: the page pool's own counters, hit tokens over
hit + miss tokens, differenced over the window, in percent."""


def read(m):
    if m.get("kind") != "serve":
        return None
    hit, miss = m.get("hit_tokens", 0), m.get("miss_tokens", 0)
    if hit + miss <= 0:
        return None
    return 100.0 * hit / (hit + miss)
