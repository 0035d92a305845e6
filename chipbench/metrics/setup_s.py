"""Set-up seconds: inputs made on the device, warm-up of every shape the
traffic uses (compiles included where the cache has none), host clock."""


def read(rec):
    return rec["setup_s"]
