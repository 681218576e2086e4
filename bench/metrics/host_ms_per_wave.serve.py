"""Host milliseconds per wave spent packing and unpacking: the
``serve.wave.pack`` and ``serve.wave.unpack`` spans of ``repro.obs``
in the window, over the engine's ``waves`` counter."""

SPANS = ("serve.wave.pack", "serve.wave.unpack")


def read(ctx):
    waves = ctx.window.get("waves")
    us = [e["dur"] for e in ctx.spans if e["name"] in SPANS]
    if not waves or not us:
        return None
    return sum(us) / 1e3 / waves
