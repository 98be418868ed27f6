"""build_s: host-clock seconds of the index build, from the call of
``repro.api.build`` to the index being ready on the device."""


def read(run):
    return run.build_s
