"""scale x sum(num facts) / sum(den facts); with no `den`, the sum itself
(a count or a value read as it is).  No numerator fact, a missing
denominator fact or a zero denominator means there was nothing to read:
returns None."""


def read(facts: dict, spec: dict):
    nums = [facts[k] for k in spec["num"] if k in facts]
    if not nums:
        return None
    if not spec["den"]:
        return spec["scale"] * sum(nums)
    if any(k not in facts for k in spec["den"]):
        return None
    den = sum(facts[k] for k in spec["den"])
    return spec["scale"] * sum(nums) / den if den > 0 else None
