"""Plain references, one module per configuration (`<config>.py`), and
the blocks they share (`plain.py`). Nothing here imports the program."""
