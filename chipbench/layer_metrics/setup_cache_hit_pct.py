"""Model step: the persistent compile cache in set-up — of the
``jit:compile`` spans ``setup_compile_s`` sums, those the cache
answered (``cache: hit``) over those it was asked (``hit`` + ``miss``);
a program compiled with the cache off counts in neither."""

from chipbench.layer_metrics.setup_serve_run_s import compiles


def read(obs):
    verdicts = [(span.get("attrs") or {}).get("cache")
                for span in compiles(obs)]
    hits, misses = verdicts.count("hit"), verdicts.count("miss")
    if not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
