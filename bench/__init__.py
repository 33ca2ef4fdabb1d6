"""The chip benchmark of the PDF pipeline; ``bench/run.py`` is its command."""
