SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= ? AND l_shipdate < ?
  AND l_discount BETWEEN ? AND ?
  AND l_quantity < ?
