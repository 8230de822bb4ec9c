"""engine.overlap_share.offline: the share of microbatches injected in the
window while an earlier microbatch's logits were still unfetched, i.e.
launched before the engine blocked on its predecessor (the deferred
fetch), from the engine's registry counters (``engine.mb_overlapped`` /
``engine.mb_injected`` x 100), in percent.  Nothing to read where the
program keeps no ``engine.mb_overlapped``."""


def read(ctx):
    mbs = ctx.counters.get("engine.mb_injected", 0)
    overlapped = ctx.counters.get("engine.mb_overlapped")
    if not mbs or overlapped is None:
        return None
    return 100.0 * overlapped / mbs
