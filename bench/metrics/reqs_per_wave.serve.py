"""Requests per wave in the window: completions over the engine's
``waves`` counter (``repro.serve.graph``'s wave packer)."""


def read(ctx):
    waves = ctx.window.get("waves")
    if not waves:
        return None
    return ctx.window["completed"] / waves
