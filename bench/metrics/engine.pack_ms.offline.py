"""engine.pack_ms.offline: host time to gather one microbatch's rows and
put them on the device (the program's ``engine.pack`` span), per
microbatch injected in the window, from the engine's registry counters
(``engine.pack_ns`` / ``engine.mb_injected``), in ms.  Nothing to read
where the program keeps no ``engine.pack_ns``."""


def read(ctx):
    mbs = ctx.counters.get("engine.mb_injected", 0)
    ns = ctx.counters.get("engine.pack_ns")
    if not mbs or ns is None:
        return None
    return ns / mbs / 1e6
