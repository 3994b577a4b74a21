"""Cell kinds: each module drives one of the program's entry points for a
mix of its kind (``mix["kind"]``)."""
