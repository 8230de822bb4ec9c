"""engine.host_ms.offline: the engine step's host time per microbatch
injected in the window, outside the wait for its logits (the program's
``engine.step`` span less its ``engine.fetch`` spans):
(``engine.step_ns`` - ``engine.fetch_ns``) / ``engine.mb_injected``, in
ms.  Nothing to read where the program keeps no such counters."""


def read(ctx):
    mbs = ctx.counters.get("engine.mb_injected", 0)
    step = ctx.counters.get("engine.step_ns")
    fetch = ctx.counters.get("engine.fetch_ns")
    if not mbs or step is None or fetch is None:
        return None
    return (step - fetch) / mbs / 1e6
