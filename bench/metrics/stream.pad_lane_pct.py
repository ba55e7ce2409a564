"""Share of the reference lanes the monitoring session's kernel launches
ran that carried no sample, over the window: 100 x (1 - lanes fed /
lanes launched), from the session's own counters
(``StreamSession.counters()``: ``lanes_fed``, the reference samples of
each launch; ``lanes_launched``, the tile width it ran, rounded up to
whole ``block_m`` blocks). None when the program counts no lanes or
launched no kernel."""


def read(rec):
    c = rec["counters"]
    launched = c.get("lanes_launched")
    if not launched or "lanes_fed" not in c:
        return None
    return 100.0 * (1.0 - c["lanes_fed"] / launched)
