"""100 x (1 - part / whole), e.g. the device's idle share from its busy
time and the traced window.  Nothing traced: None."""


def read(facts: dict, spec: dict):
    part, whole = facts.get(spec["part"]), facts.get(spec["whole"])
    if part is None or not whole or part <= 0:
        return None
    return 100.0 * (1.0 - part / whole)
