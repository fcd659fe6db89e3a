"""Decode cycle: device ms of the decode-cycle program per cycle, in the
traced window (module name ``decode_cycle``)."""
from bench.readers import program_ms_per


def read(run):
    return program_ms_per(run, ("decode_cycle",))
