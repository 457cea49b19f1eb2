"""Device: the share of the chip's op seconds, in the traced slice, that
lies under one of the program's `acp.<layer>` scopes, in %: how much of the
busy time the program can name. The twin of `idle_named_share`."""

from .. import device_scopes


def read(run):
    return device_scopes.device_named_share(run)
