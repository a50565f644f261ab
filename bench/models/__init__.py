"""Program adapters, one module per model kind (``"model"`` in a
configuration file): they build the system under test from a
configuration file and a traffic mix, and hand it the seeded weights."""
