"""Mean of a sampled /statusz gauge over the window, as a share of an engine size."""


def read(metric: dict, facts: dict):
    g = [s[metric["gauge"]] for s in facts.get("gauges", [])]
    if not g:
        return None
    return 100.0 * (sum(g) / len(g)) / float(facts["server"][metric["of"]])
