"""Paper reproduction package."""
