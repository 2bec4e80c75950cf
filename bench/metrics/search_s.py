"""Device seconds per product of the ops under the ``numeric.search``
named scope: the warm numeric phase's search of each product's key among
the structure's keys, with its miss test (device trace, ``scopes.py``)."""

import scopes


def read(ctx):
    return scopes.per_product(ctx, "numeric.search")
