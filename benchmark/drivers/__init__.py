"""One driver per kind of run (today: serve); a traffic mix names its driver."""
