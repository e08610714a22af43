"""Share of prompt blocks the prefix cache served in the window
(``PrefixCache.hits / (hits + misses)``, counted per block)."""


def read(run):
    h, m = run.counters.get("prefix_block_hits", 0), run.counters.get("prefix_block_misses", 0)
    return 100.0 * h / (h + m) if h + m else None
