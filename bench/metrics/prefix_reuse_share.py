"""Cache layer (``core/paged.py``, the trie in ``runtime/serve.py``): the
share of admitted prompt tokens that were found resident in the prefix
cache, reused / (reused + new), from the engine's ``prefix_stats``
counters taken as their difference over the window, in percent."""


def read(run):
    d = {k: run.prefix_after[k] - run.prefix_before[k]
         for k in ("reused_tokens", "new_tokens")}
    total = d["reused_tokens"] + d["new_tokens"]
    return 100.0 * d["reused_tokens"] / total if total else None
