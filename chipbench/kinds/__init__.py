"""One driver per kind of cell (``train``, ``serve``), found by the
``kind`` key of the cell's traffic file."""
