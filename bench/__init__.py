"""The chip benchmark of the ProFe federation round (see ``harness``)."""
