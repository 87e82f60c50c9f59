"""A host clock or a counter the job recorded under ``key``."""


def read(spec, result):
    return result["clocks"].get(spec["key"])
