"""Share of the joins' pairs that the delta-by-arena product found
(``late_pairs`` / ``pairs``): a left row (an auction, a person) that was
applied after rows it matches, which is what cross-lane order makes
common and what the arena index is for. 0 would mean the cell never
drives that product."""

import nexmark_model


def read(run):
    m = nexmark_model.in_window(run)
    if m is None or m["pairs"] <= 0:
        return None
    return 100.0 * m["late_pairs"] / m["pairs"]
