"""One module per generator kind; the traffic file's ``generator`` key names it."""
