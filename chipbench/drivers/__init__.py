"""One driver per kind of cell: ``price``, ``serve``."""
