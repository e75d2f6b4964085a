"""Mean duration per cell of the program's own ``store.append`` span
(``repro.dse.campaign``), in ms: one record appended and fsynced."""


def read(ctx):
    spans = ctx.get("spans", {}).get("store.append")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
