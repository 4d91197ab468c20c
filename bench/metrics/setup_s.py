"""Set-up seconds: process start to the end of the warm-up drain (imports,
weights made on the device, every program compiled or loaded from the
persistent cache and run once)."""


def read(run):
    return run.setup_s
