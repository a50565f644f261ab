"""Plain references, one module per model kind (``"model"`` in a
configuration file). A reference imports nothing of the program."""
