"""Bytes one stage's halo exchange sends from the busiest chip, all
fields, in MB (10^6 bytes): the program's ``SimStats.halo_bytes_stage``
counter for the last plan of the run."""


def read(run):
    b = run.layer.get("halo_bytes_stage")
    return 1e-6 * b if b else None
