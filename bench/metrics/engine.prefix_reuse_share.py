"""Share of the suffix engine's prefix requests served from its prefix
trie: (hits + extensions) / (hits + extensions + misses), from the trie's
own counters (``core.engine.PrefixTrie``).  The counters run from the
engine's construction, so the share covers every step of the run (warm-up
and the whole window), not the traced steps alone.  ``None`` where the
engine has no trie or took no suffix path."""


def read(r):
    trie = getattr(r.job.engine, "trie", None)
    if trie is None:
        return None
    reused = trie.hits + trie.extensions
    if not reused + trie.misses:
        return None
    return 100.0 * reused / (reused + trie.misses)
