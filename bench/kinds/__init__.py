"""One module per kind of traffic (the ``kind`` key of a traffic file)."""
