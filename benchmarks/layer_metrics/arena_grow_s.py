"""Seconds the arena's row buffers spent growing during set-up: the
span family `arena.grow` (`DeviceArena._grow_to`, the pad of every
buffer to its new capacity, to the device's last byte), summed since
the process started. None where the program has no such span."""

from ._arena import grow_seconds


def read(ctx):
    return grow_seconds()
