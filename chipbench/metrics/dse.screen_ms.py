"""Mean duration per campaign of the program's own ``screen.jax`` span
(``repro.dse.campaign``), in ms. The screen returns NumPy, so the span
covers the device call."""


def read(ctx):
    spans = ctx.get("spans", {}).get("screen.jax")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
