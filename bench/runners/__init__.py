"""Cell runners, one module per kind of traffic (a traffic file's ``runner``)."""
