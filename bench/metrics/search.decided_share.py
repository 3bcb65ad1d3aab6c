"""Share of pairs that end with a certified EQ or NEQ verdict (not UNK)."""


def read(run):
    pairs = [a for a in run.window_answers if a.is_pair]
    if not pairs:
        return None
    return sum(a.verdict is not None and a.certified for a in pairs) / len(pairs)
