"""Start of the process to the start of the window: server start (with the
weights made on the chip), the check, warm-up and the ramp."""
UNIT = "s"


def read(record):
    return record.t0 - record.t_process
