"""Process start to the start of the measured window."""


def value(record):
    return record["setup_s"]
