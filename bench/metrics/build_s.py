"""build_s: weights drawn on the device plus the program's offline
parity encode (``ModelStepper``), each synced, host clock."""


def read(run):
    return run.setup["weights_s"] + run.setup["encode_s"]
